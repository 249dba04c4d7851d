"""From a profiler trace to the numbers the per-layer metrics read.

``load(path)`` reads one ``.xplane.pb`` file with JAX's own reader and
keeps, on the trace's one clock (nanoseconds):

* ``ops``: per device plane (``/device:TPU:0`` ...), every operation of its
  ``XLA Ops`` line as numpy arrays of start, end and an op-name code (the
  HLO name before `` = ``), with the names in ``names``;
* ``modules``: per device plane, the program executions of its
  ``XLA Modules`` line as (start, end, name);
* ``spans``: the host annotations whose names start with ``bench:`` (the
  benchmark's own ``TraceAnnotation`` spans), as (start, end, name).

The device's clock runs a millisecond or two apart from the host's in
these traces, which matters for dispatches tens of milliseconds long.
:func:`align` measures the offset per device by pairing the dispatch
spans, in order, with the scan programs they launched, and shifts that
device's times onto the host clock.

The reductions are plain interval arithmetic, kept here so every run
computes them the same way: the union of the intervals in which some
operation ran, the part of that union inside a set of spans, the busiest
operations, and the longest idle gaps, each named by the innermost
benchmark span that covers it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SCAN_MODULE = "jit_run("  # the mechanism scans' jitted entry point
MAX_OFFSET = 20_000_000  # ns; the clocks differ by a few ms at most
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
# Control-flow ops enclose the ops of their body: they count towards busy
# time (the union) but are left out of the busiest-operation list.
CONTAINERS = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class Ops:
    start: np.ndarray   # int64 ns
    end: np.ndarray     # int64 ns
    code: np.ndarray    # int32 index into names
    names: list


@dataclasses.dataclass
class Trace:
    ops: dict            # device plane -> Ops
    spans: list          # (start, end, name), sorted
    modules: dict = dataclasses.field(default_factory=dict)


def newest_xplane(root: str) -> str | None:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, modules = {}, [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if MODULE_LINE in lines:
                modules[plane.name] = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in lines[MODULE_LINE].events)
            line = lines.get(OP_LINE)
            if line is None:
                continue
            codes: dict[str, int] = {}
            st, du, cd = [], [], []
            for e in line.events:
                name = e.name.split(" = ", 1)[0]
                st.append(e.start_ns)
                du.append(e.duration_ns)
                cd.append(codes.setdefault(name, len(codes)))
            start = np.asarray(st, np.int64)
            ops[plane.name] = Ops(start, start + np.asarray(du, np.int64),
                                  np.asarray(cd, np.int32), list(codes))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        spans.append((s, s + int(e.duration_ns), e.name))
    spans.sort()
    return Trace(ops=ops, spans=spans, modules=modules)


def align(tr: Trace, scan_module: str = SCAN_MODULE) -> dict[str, int]:
    """Shift each device's ops and modules onto the host clock, in place;
    returns the offsets (ns) subtracted.  The k-th dispatch span launched
    the k-th scan program on every device, so the offset is the median gap
    between their starts; a device whose counts differ, or whose offset
    reads over ``MAX_OFFSET``, is left as it is."""
    disp = [sp for sp in tr.spans if sp[2].startswith(SPAN_PREFIX + "dispatch:")]
    out = {}
    for plane, mods in tr.modules.items():
        runs = [m for m in mods if m[2].startswith(scan_module)]
        off =(int(np.median([m[0] - sp[0] for m, sp in zip(runs, disp)]))
               if disp and len(runs) == len(disp) else 0)
        if abs(off) > MAX_OFFSET:  # a pairing this far off is no pairing
            off = 0
        out[plane] = off
        tr.modules[plane] = [(s - off, e - off, n) for s, e, n in mods]
        if plane in tr.ops:
            tr.ops[plane].start = tr.ops[plane].start - off
            tr.ops[plane].end = tr.ops[plane].end - off
    return out


# ---------------------------------------------------------------------------
# Interval arithmetic (disjoint sorted intervals as a pair of arrays)
# ---------------------------------------------------------------------------


def union(start, end) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into disjoint, sorted (starts, ends)."""
    start, end = np.asarray(start, np.int64), np.asarray(end, np.int64)
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = s[1:] > e[:-1]
    return s[np.r_[True, new]], e[np.r_[new, True]]


def clip(merged, lo: int, hi: int):
    s, e = merged
    keep = (e > lo) & (s < hi)
    return np.maximum(s[keep], lo), np.minimum(e[keep], hi)


def total(merged) -> int:
    s, e = merged
    return int((e - s).sum())


def busy_before(merged, t) -> np.ndarray:
    """Busy nanoseconds of ``merged`` before each time in ``t``."""
    s, e = merged
    t = np.asarray(t, np.int64)
    if s.size == 0:
        return np.zeros(t.shape, np.int64)
    cum = np.concatenate([[0], np.cumsum(e - s)])
    i = np.searchsorted(s, t, side="right")
    j = np.maximum(i - 1, 0)
    return np.where(i > 0, cum[j] + np.clip(t - s[j], 0, (e - s)[j]), 0)


def overlap(merged, windows) -> int:
    """Length of ``merged`` inside the union of ``windows`` ((start, end,
    ...) tuples)."""
    if not windows:
        return 0
    ws, we = union([w[0] for w in windows], [w[1] for w in windows])
    return int((busy_before(merged, we) - busy_before(merged, ws)).sum())


def device_busy(tr: Trace, plane: str, lo: int, hi: int):
    o = tr.ops[plane]
    return clip(union(o.start, o.end), lo, hi)


def busy_per_device(tr: Trace, lo: int, hi: int) -> dict[str, int]:
    """Nanoseconds in which some operation ran, per device, in [lo, hi)."""
    return {d: total(device_busy(tr, d, lo, hi)) for d in tr.ops}


def spans_named(tr: Trace, name: str) -> list:
    return [s for s in tr.spans if s[2] == name]


def _innermost(tr: Trace, t: int) -> str:
    cover = [sp for sp in tr.spans if sp[0] <= t < sp[1]]
    return min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover else "idle"


def _leaf_labels(tr: Trace, t: np.ndarray) -> tuple[np.ndarray, list]:
    """For each time in ``t``, the dispatch or prep span that covers it
    (these never overlap one another), else ``bench:run`` where a run span
    covers it, else ``other``."""
    leaves = [sp for sp in tr.spans
              if sp[2].startswith(("bench:dispatch:", "bench:prep"))]
    runs = [sp for sp in tr.spans if sp[2] == "bench:run"]
    labels = sorted({sp[2] for sp in leaves}) + ["bench:run", "other"]
    code = np.full(t.shape, len(labels) - 1, np.int32)
    for group, fixed in ((runs, "bench:run"), (leaves, None)):
        if not group:
            continue
        s = np.asarray([sp[0] for sp in group], np.int64)
        e = np.asarray([sp[1] for sp in group], np.int64)
        i = np.searchsorted(s, t, side="right") - 1
        hit = (i >= 0) & (t < e[np.maximum(i, 0)])
        lab = np.asarray([labels.index(fixed or sp[2]) for sp in group],
                         np.int32)
        code = np.where(hit, lab[np.maximum(i, 0)], code)
    return code, labels


def top_ops(tr: Trace, lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` (span, operation) pairs with the most device time in
    [lo, hi), summed over devices, in seconds; control-flow containers are
    left out."""
    tot: dict[str, int] = {}
    for o in tr.ops.values():
        if not o.names:
            continue
        leaf = ~np.asarray([n.startswith(CONTAINERS) for n in o.names])
        keep = (o.end > lo) & (o.start < hi) & leaf[o.code]
        st = np.maximum(o.start[keep], lo)
        du = np.minimum(o.end[keep], hi) - st
        lab, labels = _leaf_labels(tr, st)
        pair = lab.astype(np.int64) * len(o.names) + o.code[keep]
        sums = np.bincount(pair, weights=du)
        for p in np.flatnonzero(sums):
            name = (labels[p // len(o.names)].removeprefix(SPAN_PREFIX)
                    + "/" + o.names[p % len(o.names)])
            tot[name] = tot.get(name, 0) + int(sums[p])
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_gaps(tr: Trace, lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` longest stretches of [lo, hi) in which no device ran an
    operation, each named by the innermost benchmark span covering its
    midpoint (``idle`` where none does), in seconds."""
    if tr.ops:
        s = np.concatenate([o.start for o in tr.ops.values()])
        e = np.concatenate([o.end for o in tr.ops.values()])
        bs, be = clip(union(s, e), lo, hi)
    else:
        bs = be = np.zeros(0, np.int64)
    gs = np.r_[lo, be]
    ge = np.r_[bs, hi]
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    out = []
    for i in np.argsort(gs - ge, kind="stable")[:k]:
        name = _innermost(tr, int((gs[i] + ge[i]) // 2))
        out.append([name.removeprefix(SPAN_PREFIX), int(ge[i] - gs[i]) / 1e9])
    return out
