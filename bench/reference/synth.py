"""Plain numpy trace synthesis: the benchmark's frozen copy of the workload
definitions (paper §6.1 plus the extended families).

It imports nothing of the simulator.  Every random value is a Threefry-2x32
counter hash of a (stream key, counter) pair; a trace is generated one
kernel and one window at a time, as the workload description reads.  The
program under test must produce the same access lists from the same
(workload, seed); the benchmark's correctness check holds it to the
simulated results this trace yields.

``make_trace(app, graph, seed=..., threads=..., num_kernels=...,
windows_per_kernel=..., scale=..., cpu_reuse=..., **extra)`` returns a dict
of numpy arrays with the same keyword defaults as the program's workload
specs.

Families and graph inputs are found by name, so a new one is a new file:

* an ``app`` that is not built in is ``apps/<app>.py`` beside this file,
  whose ``make_trace(app, graph, *, seed, threads, num_kernels,
  windows_per_kernel, scale, cpu_reuse, **extra)`` returns the same dict
  (``scale`` and ``cpu_reuse`` are ``None`` where the workload leaves them
  to the family's defaults);
* a ``graph`` that is not in :data:`GRAPH_SHAPES` is ``graphs/<graph>.py``,
  whose ``make_graph(seed, scale, **extra)`` returns ``(num_nodes, edges)``
  with edges an (E, 2) int32 array sorted by source; the built-in graph,
  frontier and mtmix families then run on it unchanged.  Such a file may
  leave the study seed aside and build one graph from its own keys.

Workload keys beyond the keywords above (``extra``) go to the family or
graph file found by name; a built-in family or graph given one raises
``TypeError``.  Those files may use this module's helpers (the Threefry
streams, ``_Trace``, ``_pad``) and import nothing of the program.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import pathlib
import re
import zlib

import numpy as np

# Window geometry (§5.4): slot widths of one partial-kernel window.
AR, AW, BR, BW = 256, 256, 64, 64

GRAPH_SHAPES = {  # SNAP node and edge counts (§6.1)
    "enron": (73384, 367662),
    "arxiv": (10484, 28984),
    "gnutella": (45374, 109410),
}
IMDB_TABLES, IMDB_TUPLES, IMDB_FIELDS = 64, 65536, 32
VPL = 8   # 8-byte vertex values per 64 B line
EPL = 8   # 8-byte CSR edges per line
TUPLE_LINES = IMDB_FIELDS * 8 // 64

HERE = pathlib.Path(__file__).resolve().parent
FILE_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

GRAPH_APPS = ("pagerank", "radii", "components")
FRONTIER_APPS = ("bfs", "sssp")
HTAP_APPS = ("htap128", "htap192", "htap256")
BUILT_IN_APPS = GRAPH_APPS + FRONTIER_APPS + HTAP_APPS + ("mtmix", "htap_stream")

# ---------------------------------------------------------------------------
# Threefry-2x32 counter streams
# ---------------------------------------------------------------------------

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)


def _threefry(k0, k1, ctr):
    k0, k1 = np.uint32(k0), np.uint32(k1)
    ks = (k0, k1, np.uint32(0x1BD11BDA) ^ k0 ^ k1)
    x0 = np.asarray(ctr, np.uint32) + k0
    x1 = np.zeros_like(x0) + k1
    for d in range(5):
        for r in _ROT_A if d % 2 == 0 else _ROT_B:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(d + 1) % 3]
        x1 = x1 + ks[(d + 2) % 3] + np.uint32(d + 1)
    return x0


def _u01(key, ctr):
    return (_threefry(*key, ctr) >> np.uint32(8)).astype(np.float32) \
        * np.float32(2.0 ** -24)


def _mod(key, ctr, bound):
    return (_threefry(*key, ctr) % np.asarray(bound, np.uint32)).astype(np.int32)


def _keys(app, graph, seed, streams):
    k1 = (seed * 2654435761 + 0x9E3779B9) & 0xFFFFFFFF
    return {s: (zlib.crc32(f"{app}/{graph or ''}/{s}".encode()) & 0xFFFFFFFF, k1)
            for s in streams}


def _ctr(n, base=0):
    return (np.arange(n, dtype=np.uint32) + np.uint32(base)).astype(np.uint32)


def _one(x):
    return np.asarray([x], np.uint32)


# ---------------------------------------------------------------------------
# Inputs: power-law graphs at SNAP counts, the IMDB layout
# ---------------------------------------------------------------------------


@functools.cache
def _by_name(kind, name):
    """The module ``<kind>/<name>.py`` beside this file, loaded once."""
    path = HERE / kind / f"{name}.py"
    if not (isinstance(name, str) and FILE_NAME.match(name) and path.is_file()):
        raise ValueError(f"unknown {kind[:-1]} {name!r}: not built in and "
                         f"no {kind}/{name}.py beside {__name__}")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_graph(name, seed, scale=1.0, **extra):
    if name not in GRAPH_SHAPES:
        n, ed = _by_name("graphs", name).make_graph(seed, scale, **extra)
        ed = np.asarray(ed)
        if (ed.dtype != np.int32 or ed.ndim != 2 or ed.shape[1] != 2
                or np.any(ed[1:, 0] < ed[:-1, 0])):
            raise ValueError(f"graph {name!r}: edges must be an (E, 2) int32 "
                             "array sorted by source")
        return int(n), ed
    if extra:
        raise TypeError(f"graph {name!r} takes no keys {sorted(extra)}")
    nodes, edges = GRAPH_SHAPES[name]
    n = max(16, int(nodes * scale))
    e = max(32, int(edges * scale))
    rng = np.random.default_rng(seed ^ zlib.crc32(name.encode()) & 0xFFFF)
    probs = np.arange(1, n + 1, dtype=np.float64) ** -0.9
    probs /= probs.sum()
    dst = rng.choice(n, size=e, p=probs).astype(np.int32)
    src = rng.integers(0, n, size=e).astype(np.int32)
    perm = rng.permutation(n).astype(np.int32)
    ed = np.stack([perm[src], perm[dst]], axis=1)
    return n, ed[np.argsort(ed[:, 0], kind="stable")]


def _graph_layout(n, E):
    vl, fl, el = -(-n // VPL), -(-n // 64), -(-E // EPL)
    return vl, fl, el


# ---------------------------------------------------------------------------
# Window assembly
# ---------------------------------------------------------------------------


def _pad(ids, width):
    out = np.full((width,), -1, np.int32)
    k = min(len(ids), width)
    out[:k] = ids[:k]
    return out


class _Trace:
    """Per-window slot arrays filled window by window."""

    def __init__(self, K, wpk):
        W = K * wpk
        self.K, self.wpk = K, wpk
        self.pim_reads = np.full((W, AR), -1, np.int32)
        self.pim_writes = np.full((W, AW), -1, np.int32)
        self.cpu_reads = np.full((W, BR), -1, np.int32)
        self.cpu_writes = np.full((W, BW), -1, np.int32)
        self.pre = [None] * K

    def finish(self, name, threads, num_lines, p):
        n_pim = ((self.pim_reads >= 0).sum(1)
                 + (self.pim_writes >= 0).sum(1)).astype(np.float32)
        n_cpu = ((self.cpu_reads >= 0).sum(1)
                 + (self.cpu_writes >= 0).sum(1)).astype(np.float32)
        W = self.K * self.wpk
        j = np.arange(W) % self.wpk
        return dict(
            name=name, threads=threads, num_lines=num_lines,
            pim_reads=self.pim_reads, pim_writes=self.pim_writes,
            cpu_reads=self.cpu_reads, cpu_writes=self.cpu_writes,
            kernel_id=np.arange(W) // self.wpk,
            kernel_start=j == 0, kernel_end=j == self.wpk - 1,
            pre_lines=[np.unique(x.astype(np.int64)) for x in self.pre],
            pim_instr=n_pim * np.float32(p["pim_ipw"]),
            cpu_instr=(n_cpu * np.float32(p["cpu_reuse"])
                       * np.float32(p["cpu_ipw"])
                       + np.float32(threads * p["cpu_serial_instr"])),
            cpu_priv=np.full((W,), np.float32(threads * p["priv_apw"]),
                             np.float32),
            cpu_priv_miss_rate=np.float32(p["cpu_priv_miss_rate"]),
            cpu_reuse=np.float32(p["cpu_reuse"]))


# ---------------------------------------------------------------------------
# Families
# ---------------------------------------------------------------------------


def _graph(app, graph, threads, K, wpk, seed, scale, reuse, **extra):
    n, edges = make_graph(graph, seed, scale, **extra)
    E = len(edges)
    vl, fl, el = _graph_layout(n, E)
    pn, fb, eb = vl, 2 * vl, 2 * vl + fl
    raw_w, hot_bias = {"pagerank": (0.35, 0.0), "radii": (0.6, 0.35),
                       "components": (1.5, 0.85)}[app]
    ffrac = {"pagerank": 1.0, "radii": 0.45, "components": 0.6}[app]
    hi = [max(1, E - max(64, int(E * ffrac ** (k % 6)))) for k in range(K)]
    raw_int = int(raw_w)
    raw_frac = raw_w - raw_int
    R = raw_int + (1 if raw_frac > 0 else 0)
    epw, pool_n, reads_n, bk_n = 60, 600, 44, 4
    key = _keys(app, graph, seed, ("e0", "bk", "pool", "rawn", "rawhot",
                                   "rawhotv", "rawuni", "safe", "crs"))
    t = _Trace(K, wpk)
    pool = _mod(key["pool"], _ctr(pool_n), n)
    w = 0
    for k in range(K):
        e0 = int(_mod(key["e0"], _one(k), np.asarray([hi[k]], np.uint32))[0])
        bk = _mod(key["bk"], _ctr(bk_n, k * bk_n), n)
        t.pre[k] = np.concatenate([fb + bk // 64, bk // VPL])
        for j in range(wpk):
            eidx = (np.arange(epw, dtype=np.int32) + np.int32(e0 + j * epw)) % E
            src, dst = edges[eidx, 0], edges[eidx, 1]
            reads = np.empty((2 * epw,), np.int32)
            reads[0::2] = eb + eidx // EPL
            reads[1::2] = dst // VPL
            t.pim_reads[w] = _pad(reads, AR)
            t.pim_writes[w] = _pad(pn + (src if app == "pagerank" else dst)
                                   // VPL, AW)
            rctr = _ctr(R, w * R)
            coin = _u01(key["rawn"], _one(w))[0] < np.float32(raw_frac)
            rvalid = (np.arange(R) < raw_int) | ((np.arange(R) == raw_int) & coin)
            hot = _u01(key["rawhot"], rctr) < np.float32(hot_bias)
            v_hot = edges[_mod(key["rawhotv"], rctr, E), 1]
            v_uni = _mod(key["rawuni"], rctr, n)
            raw = np.where(rvalid, np.where(hot, v_hot, v_uni) // VPL, -1)
            safe = _mod(key["safe"], _one(w), n)
            t.cpu_writes[w] = _pad(np.concatenate([raw, pn + safe // VPL]), BW)
            cv = pool[_mod(key["crs"], _ctr(reads_n, w * reads_n), pool_n)]
            h = reads_n // 2
            t.cpu_reads[w] = _pad(np.concatenate([pn + cv[:h] // VPL,
                                                  fb + cv[h:] // 64]), BR)
            w += 1
    return t.finish(f"{app}-{graph}", threads, eb + el, dict(
        pim_ipw=3.0, cpu_ipw=6.0, cpu_serial_instr=420.0, priv_apw=160.0,
        cpu_priv_miss_rate=0.002, cpu_reuse=reuse))


def _frontier(app, graph, threads, K, wpk, seed, scale, reuse, **extra):
    n, edges = make_graph(graph, seed, scale, **extra)
    E = len(edges)
    vl, fl, el = _graph_layout(n, E)
    pn, fb, eb = vl, 2 * vl, 2 * vl + fl
    peak, pos, width, relax, qraw = {"bfs": (110, 0.30, 0.20, 0.45, 0.25),
                                     "sssp": (90, 0.38, 0.33, 0.70, 0.90)}[app]
    epw = [max(6, int(peak * math.exp(-0.5 * ((k - pos * K) / (width * K)) ** 2)))
           for k in range(K)]
    S, pool_n, reads_n, bk_n = max(epw), 600, 36, 6
    key = _keys(app, graph, seed, ("f0", "relax", "qsafe", "qraw", "qrawv",
                                   "pool", "crs", "bk"))
    t = _Trace(K, wpk)
    pool = _mod(key["pool"], _ctr(pool_n), n)
    w = 0
    for k in range(K):
        f0 = int(_mod(key["f0"], _one(k), E)[0])
        bk = _mod(key["bk"], _ctr(bk_n, k * bk_n), n)
        t.pre[k] = np.concatenate([fb + bk // 64, bk // VPL])
        for j in range(wpk):
            slot = np.arange(S, dtype=np.int32)
            alive = slot < epw[k]
            eidx = (slot + np.int32(f0 + j * epw[k])) % E
            dst = edges[eidx, 1]
            reads = np.empty((2 * S,), np.int32)
            reads[0::2] = np.where(alive, eb + eidx // EPL, -1)
            reads[1::2] = np.where(alive, dst // VPL, -1)
            t.pim_reads[w] = _pad(reads, AR)
            relaxed = _u01(key["relax"], _ctr(S, w * S)) < np.float32(relax)
            t.pim_writes[w] = _pad(np.where(alive & relaxed, pn + dst // VPL, -1),
                                   AW)
            qv = _mod(key["qsafe"], _ctr(2, w * 2), n)
            qcoin = _u01(key["qraw"], _one(w))[0] < np.float32(qraw)
            qrv = _mod(key["qrawv"], _one(w), n)
            raw = np.where(qcoin, qrv // VPL, -1)
            t.cpu_writes[w] = _pad(np.concatenate([fb + qv // 64, raw]), BW)
            cv = pool[_mod(key["crs"], _ctr(reads_n, w * reads_n), pool_n)]
            h = reads_n // 2
            t.cpu_reads[w] = _pad(np.concatenate([cv[:h] // VPL,
                                                  fb + cv[h:] // 64]), BR)
            w += 1
    return t.finish(f"{app}-{graph}", threads, eb + el, dict(
        pim_ipw=2.5, cpu_ipw=6.0, cpu_serial_instr=380.0, priv_apw=150.0,
        cpu_priv_miss_rate=0.002, cpu_reuse=reuse))


def _imdb(scale):
    tuples = int(IMDB_TUPLES * scale)
    table_lines = int(IMDB_TUPLES * scale) * TUPLE_LINES
    hash_lines = max(64, table_lines // 4)
    hash_base = IMDB_TABLES * table_lines
    return tuples, hash_base, hash_lines, hash_base + hash_lines


def _htap(app, threads, K, wpk, seed, scale, reuse):
    tuples, hb, hl, total = _imdb(scale)
    TL, T = TUPLE_LINES, IMDB_TABLES
    intensity = int(app.replace("htap", "")) / 128.0
    n_scan, n_probe, n_wr = 35, 12, max(8, int(40 * intensity))
    txn_writes, txn_hot, txn_reads, burst_n, burst_hot, pool_n = 2, 1, 26, 8, 3, 500
    key = _keys(app, None, seed, ("tbl", "cur", "btab", "btup", "bfld", "probe",
                                  "wrh", "twtab", "twtup", "twfld", "ptab",
                                  "ptup", "pfld", "txr"))

    def tline(tab, tup, fld):
        return ((tab * tuples + tup) * TL + fld).astype(np.int32)

    t = _Trace(K, wpk)
    ictr = _ctr(pool_n)
    pool = tline(_mod(key["ptab"], ictr, T), _mod(key["ptup"], ictr, tuples),
                 _mod(key["pfld"], ictr, TL))
    w = 0
    for k in range(K):
        table = int(_mod(key["tbl"], _one(k), T)[0])
        cur0 = int(_mod(key["cur"], _one(k), max(1, tuples - 1))[0])
        bctr = _ctr(burst_n, k * burst_n)
        btab = np.where(np.arange(burst_n) < burst_hot, table,
                        _mod(key["btab"], bctr, T))
        t.pre[k] = tline(btab, _mod(key["btup"], bctr, tuples),
                         _mod(key["bfld"], bctr, TL))
        for j in range(wpk):
            s = np.arange(n_scan, dtype=np.int32)
            tup = (cur0 + j * (n_scan // TL) + s // TL) % tuples
            scan = tline(np.full_like(s, table), tup, s % TL)
            probe = hb + _mod(key["probe"], _ctr(n_probe, w * n_probe), hl)
            t.pim_reads[w] = _pad(np.concatenate([scan, probe]), AR)
            t.pim_writes[w] = _pad(hb + _mod(key["wrh"], _ctr(n_wr, w * n_wr),
                                             hl), AW)
            tctr = _ctr(txn_writes, w * txn_writes)
            ttab = np.where(np.arange(txn_writes) < txn_hot, table,
                            _mod(key["twtab"], tctr, T))
            t.cpu_writes[w] = _pad(tline(ttab, _mod(key["twtup"], tctr, tuples),
                                         _mod(key["twfld"], tctr, TL)), BW)
            t.cpu_reads[w] = _pad(pool[_mod(key["txr"], _ctr(txn_reads,
                                                             w * txn_reads),
                                             pool_n)], BR)
            w += 1
    return t.finish(app, threads, total, dict(
        pim_ipw=2.5 + 1.5 * intensity, cpu_ipw=12.0, cpu_serial_instr=500.0,
        priv_apw=220.0, cpu_priv_miss_rate=0.0015, cpu_reuse=reuse))


def _stream(app, threads, K, wpk, seed, scale, reuse):
    tuples, hb, hl, total = _imdb(scale)
    TL, TOT = TUPLE_LINES, IMDB_TABLES * tuples
    apw, lag, n_scan, n_probe, n_wr = 6, 96, 40, 10, 24
    idx_writes, txn_reads, recent, burst_n = 2, 24, 512, 8
    key = _keys(app, None, seed, ("probe", "wrh", "idxw", "txr", "burst"))

    def gtline(g, fld):
        return (g * TL + fld).astype(np.int32)

    t = _Trace(K, wpk)
    for k in range(K):
        tail_k = (k * wpk * apw) % TOT
        b = _mod(key["burst"], _ctr(burst_n, k * burst_n), 64)
        g = (tail_k + TOT - 1 - b) % TOT
        t.pre[k] = gtline(g, np.zeros_like(g))
    for w in range(K * wpk):
        tail = (w * apw) % TOT
        s = np.arange(n_scan, dtype=np.int32)
        scan = gtline((tail + TOT - lag - s) % TOT, s % TL)
        probe = hb + _mod(key["probe"], _ctr(n_probe, w * n_probe), hl)
        t.pim_reads[w] = _pad(np.concatenate([scan, probe]), AR)
        t.pim_writes[w] = _pad(hb + _mod(key["wrh"], _ctr(n_wr, w * n_wr), hl),
                               AW)
        a = np.arange(apw, dtype=np.int32)
        appends = gtline((tail + a) % TOT, np.zeros_like(a))
        idxw = hb + _mod(key["idxw"], _ctr(idx_writes, w * idx_writes), hl)
        t.cpu_writes[w] = _pad(np.concatenate([appends, idxw]), BW)
        r = _mod(key["txr"], _ctr(txn_reads, w * txn_reads), recent)
        t.cpu_reads[w] = _pad(gtline((tail + TOT - 1 - r) % TOT, r % TL), BR)
    return t.finish(app, threads, total, dict(
        pim_ipw=4.0, cpu_ipw=12.0, cpu_serial_instr=500.0, priv_apw=220.0,
        cpu_priv_miss_rate=0.0015, cpu_reuse=reuse))


def _mtmix(app, graph, threads, K, wpk, seed, scale, reuse, **extra):
    if K < 2:
        raise ValueError("mtmix needs num_kernels >= 2")
    n, edges = make_graph(graph, seed, scale, **extra)
    E = len(edges)
    vl, fl, el = _graph_layout(n, E)
    tl = 2 * vl + fl
    a_pc, a_pn, a_fr = 0, vl, 2 * vl
    b_pc, b_pn, b_fr = tl, tl + vl, tl + 2 * vl
    eb = 2 * tl
    ka, kb = (K + 1) // 2, K // 2
    hi_b = [max(1, E - max(64, int(E * 0.6 ** (k % 6)))) for k in range(kb)]
    epw, a_raw_frac, b_raw_int, b_raw_frac, b_hot = 60, 0.5, 0, 0.7, 0.5
    pool_n, reads_n, bk_n = 600, 40, 4
    key = _keys(app, graph, seed, (
        "e0A", "e0B", "bkA", "bkB", "poolA", "poolB", "rawnA", "rawuniA",
        "safeA", "rawnB", "rawhotB", "rawhotvB", "rawuniB", "safeB", "crsA",
        "crsB"))
    t = _Trace(K, wpk)
    poolA = _mod(key["poolA"], _ctr(pool_n), n)
    poolB = _mod(key["poolB"], _ctr(pool_n), n)
    Rb = b_raw_int + 1
    w = 0
    for k in range(K):
        tb, kl = k % 2 == 1, k // 2
        if tb:
            e0 = int(_mod(key["e0B"], _one(kl), np.asarray([hi_b[kl]], np.uint32))[0])
            bk = _mod(key["bkB"], _ctr(bk_n, kl * bk_n), n)
            pc, pn, fr = b_pc, b_pn, b_fr
        else:
            e0 = int(_mod(key["e0A"], _one(kl), np.asarray([1], np.uint32))[0])
            bk = _mod(key["bkA"], _ctr(bk_n, kl * bk_n), n)
            pc, pn, fr = a_pc, a_pn, a_fr
        t.pre[k] = np.concatenate([fr + bk // 64, pn + bk // VPL])
        for j in range(wpk):
            eidx = (np.arange(epw, dtype=np.int32) + np.int32(e0 + j * epw)) % E
            src, dst = edges[eidx, 0], edges[eidx, 1]
            reads = np.empty((2 * epw,), np.int32)
            reads[0::2] = eb + eidx // EPL
            reads[1::2] = pc + dst // VPL
            t.pim_reads[w] = _pad(reads, AR)
            t.pim_writes[w] = _pad(pn + (dst if tb else src) // VPL, AW)
            a_coin = _u01(key["rawnA"], _one(w))[0] < np.float32(a_raw_frac)
            a_v = _mod(key["rawuniA"], _one(w), n)
            a_raw = np.where(a_coin, a_pc + a_v // VPL, -1)
            a_safe = a_pn + _mod(key["safeA"], _one(w), n) // VPL
            bctr = _ctr(Rb, w * Rb)
            b_coin = _u01(key["rawnB"], _one(w))[0] < np.float32(b_raw_frac)
            b_valid = (np.arange(Rb) < b_raw_int) | ((np.arange(Rb) == b_raw_int)
                                                     & b_coin)
            hot = _u01(key["rawhotB"], bctr) < np.float32(b_hot)
            b_vh = edges[_mod(key["rawhotvB"], bctr, E), 1]
            b_vu = _mod(key["rawuniB"], bctr, n)
            b_raw = np.where(b_valid, b_pc + np.where(hot, b_vh, b_vu) // VPL, -1)
            b_safe = b_pn + _mod(key["safeB"], _one(w), n) // VPL
            t.cpu_writes[w] = _pad(np.concatenate([a_raw, a_safe, b_raw, b_safe])
                                   .astype(np.int32), BW)
            per = reads_n // 2
            cctr = _ctr(per, w * per)
            av = poolA[_mod(key["crsA"], cctr, pool_n)]
            bv = poolB[_mod(key["crsB"], cctr, pool_n)]
            q = per // 2
            t.cpu_reads[w] = _pad(np.concatenate([
                a_pn + av[:q] // VPL, a_fr + av[q:] // 64,
                b_pn + bv[:q] // VPL, b_fr + bv[q:] // 64]).astype(np.int32), BR)
            w += 1
    return t.finish(f"{app}-{graph}", threads, eb + el, dict(
        pim_ipw=3.0, cpu_ipw=6.0, cpu_serial_instr=460.0, priv_apw=200.0,
        cpu_priv_miss_rate=0.002, cpu_reuse=reuse))


def make_trace(app, graph=None, *, seed=0, threads=16, num_kernels=24,
               windows_per_kernel=3, scale=None, cpu_reuse=None,
               **extra) -> dict:
    """The trace of one workload, as a dict of numpy arrays (see module
    docstring); ``pre_lines[k]`` lists the lines kernel ``k``'s
    inter-kernel phase writes."""
    if app not in BUILT_IN_APPS:
        return _by_name("apps", app).make_trace(
            app, graph, seed=seed, threads=threads, num_kernels=num_kernels,
            windows_per_kernel=windows_per_kernel, scale=scale,
            cpu_reuse=cpu_reuse, **extra)
    if scale is None:
        scale = 0.01 if app in HTAP_APPS + ("htap_stream",) else 1.0
    if cpu_reuse is None:
        cpu_reuse = 8.0 if app == "htap_stream" else 6.0
    args = (threads, num_kernels, windows_per_kernel, seed, scale, cpu_reuse)
    if app in GRAPH_APPS:
        return _graph(app, graph, *args, **extra)
    if app in FRONTIER_APPS:
        return _frontier(app, graph, *args, **extra)
    if app == "mtmix":
        return _mtmix(app, graph, *args, **extra)
    if extra:
        raise TypeError(f"{app!r} takes no keys {sorted(extra)}")
    if app in HTAP_APPS:
        return _htap(app, *args)
    return _stream(app, *args)
