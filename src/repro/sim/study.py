"""Declarative ``Study`` experiment API with an automatic execution planner.

The paper's whole evaluation is one cross-product — workloads × coherence
mechanisms × hardware points × LazyPIM ablations (Figs. 7–13) — and this
module is the single front door for expressing any slice of it:

    from repro.api import Study, grid

    study = Study(workloads=["pagerank-arxiv", "htap128"],
                  hw=grid(offchip_bw_gbs=[16.0, 32.0, 64.0]),
                  mechanisms=("cpu", "cg", "lazypim"))
    print(study.plan().describe())   # buckets + compile budget, before running
    results = study.run()            # ResultSet of tagged SimResults
    table = results.pivot("workload", "mechanism", "speedup")

``run()`` plans execution automatically: workloads are prepared, grouped
into pow2-ish geometry buckets (:func:`repro.sim.prep.bucket_shapes`), the
hw / lazy axes are *folded into the stacked workload axis* (each padded
trace is repeated per (hw-point, lazy-point) lane), and every bucket is
dispatched through the engine's cached jitted+vmapped scans
(:func:`repro.sim.engine._sweep_fn`) — so any study, whatever its shape,
costs at most **one XLA compile per (mechanism, geometry bucket,
static-flag combo)**.  :meth:`Study.plan` returns that predicted budget
before anything runs; ``benchmarks/check_budget.py --live`` cross-checks it
against the measured :func:`repro.sim.engine.sweep_cache_sizes` deltas.

Axes
----
* ``workloads=`` — names (``"pagerank-arxiv"``, ``"htap128"``), ``(app,
  graph)`` pairs, :func:`workload` specs (per-entry threads / signature
  spec / trace kwargs), or prepared :class:`~repro.sim.prep.TraceTensors`.
* ``hw=`` — a single :class:`~repro.sim.costmodel.HWParams` (broadcast), a
  :func:`grid` cross-product helper (crossed with the workload axis), or an
  explicit list (zipped per-workload, like fig8's thread sweep).
* ``mechanisms=`` — any subset of :data:`repro.sim.engine.MECHANISMS`.
* ``lazy=`` — a single :class:`~repro.core.coherence.LazyPIMConfig` or an
  ablation list over the *traced* knobs (DBI interval/batch, commit
  exposure); mixing the static flags (``partial_commits``, ``cpuws_regs``,
  ``max_rollbacks``) in one list is a ``ValueError`` — they select a
  different compiled dataflow, so run one study per static combo and
  concatenate the :class:`ResultSet`\\ s.

Every invalid spec fails at construction with a ``ValueError`` naming the
offending entry (``tests/test_study.py``).

``run()`` returns a :class:`ResultSet`: per-point ``SimResult``\\ s tagged
with their (workload, hw-point, lazy-point) coordinates, with ``to_rows()``
/ ``pivot()`` for tabulation, ``normalized(to="cpu")`` for the paper's
CPU-normalized presentation, and ``save_json()`` / ``load_json()`` for the
golden regression artifacts.  The planner is bit-exact with the sequential
reference path (``run(engine="sequential")``, and transitively
``repro.sim.engine.run_all``) on every ``SimResult`` field.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import pathlib
from typing import Any, Iterable, Sequence

from repro.core.coherence import LazyPIMConfig
from repro.core.mechanisms import SimResult, finalize_result
from repro.core.signatures import SignatureSpec
from repro.runtime import spans
from repro.sim import engine as _engine
from repro.sim import mesh as _mesh
from repro.sim.costmodel import HWParams
from repro.sim.prep import TraceTensors, bucket_shapes, pad_trace, prepare
from repro.sim.trace import (ALL_APPS, GENERATED_GRAPHS, GRAPH_INPUTS,
                             make_trace, workload_key_error)

__all__ = [
    "Study", "StudyPlan", "StudyPoint", "ResultSet", "ResultSetSchemaError",
    "Workload", "workload", "HWGrid", "grid", "Dispatch", "BucketLanes",
    "RESULTSET_SCHEMA_VERSION",
]

# Version stamp written into every ResultSet.save_json payload.  load_json
# accepts this version and (for pre-stamp golden artifacts) a missing field;
# anything else is a named ResultSetSchemaError, never a raw KeyError.
RESULTSET_SCHEMA_VERSION = 1

_TRACE_IDS = itertools.count()


class ResultSetSchemaError(ValueError):
    """A persisted ResultSet artifact is truncated, corrupt, or from an
    incompatible schema version.  Raised by :meth:`ResultSet.load_json`
    instead of leaking ``json.JSONDecodeError`` / ``KeyError`` — callers
    (golden tests, the serve layer's artifacts) get one named error with
    the path and the reason."""


@dataclasses.dataclass(frozen=True)
class Dispatch:
    """One engine dispatch unit, handed to a ``Study.run(on_dispatch=...)``
    boundary just before it executes: which compiled scan is about to run
    (``mechanism``), through which engine, over what shape.  Dispatches are
    the natural cancellation / error-boundary granularity — the serve layer
    (:mod:`repro.serve`) checks deadlines, beats heartbeats and injects
    chaos faults here, one decision per compiled-scan execution."""

    engine: str                      # "batch" | "sequential"
    mechanism: str
    lanes: int = 1                   # stacked lanes in this dispatch
    bucket_lines: int | None = None  # batch only: the bucket's line bound
    workload: str | None = None      # sequential only: the point's workload
    devices: int = 1                 # lane-mesh size this dispatch shards over


# ---------------------------------------------------------------------------
# Workload / hardware axis specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Workload:
    """One workload entry of a study: (app, graph input) plus optional
    per-entry overrides (thread count, signature spec, trace kwargs).
    Build with :func:`workload`."""

    app: str
    graph: str | None = None
    threads: int | None = None
    spec: SignatureSpec | None = None
    trace_kw: tuple[tuple[str, Any], ...] = ()


def workload(app: str, graph: str | None = None, *,
             threads: int | None = None, spec: SignatureSpec | None = None,
             **trace_kw) -> Workload:
    """Workload spec with per-entry overrides, e.g.
    ``workload("pagerank", "arxiv", threads=4)`` for a thread-scaling study
    or ``workload("htap128", spec=SignatureSpec(sig_bits=8192))`` for a
    signature-size ablation."""
    return Workload(app, graph, threads=threads, spec=spec,
                    trace_kw=tuple(sorted(trace_kw.items())))


@dataclasses.dataclass(frozen=True)
class HWGrid:
    """A hardware cross-product axis (build with :func:`grid`): every
    combination of the named field values over a base ``HWParams``."""

    base: HWParams
    axes: tuple[tuple[str, tuple[Any, ...]], ...]

    def points(self) -> list[HWParams]:
        names = [k for k, _ in self.axes]
        return [dataclasses.replace(self.base, **dict(zip(names, combo)))
                for combo in itertools.product(*(v for _, v in self.axes))]

    def labels(self) -> list[dict[str, Any]]:
        """The varied-field values of each grid point, in point order."""
        names = [k for k, _ in self.axes]
        return [dict(zip(names, combo))
                for combo in itertools.product(*(v for _, v in self.axes))]


def grid(base: HWParams | None = None, **axes: Iterable[Any]) -> HWGrid:
    """Hardware cross-product helper: ``grid(offchip_bw_gbs=[16, 32, 64],
    pim_cores=[8, 16])`` is a 6-point hw axis over the default ``HWParams``
    (or ``base=``).  Field names are validated against ``HWParams``; points
    enumerate in the given keyword order with the last axis fastest."""
    known = {f.name for f in dataclasses.fields(HWParams)}
    for name in axes:
        if name not in known:
            raise ValueError(f"grid: unknown HWParams field {name!r} "
                             f"(know {sorted(known)})")
    if not axes:
        raise ValueError("grid needs at least one HWParams field axis")
    return HWGrid(base or HWParams(),
                  tuple((k, tuple(v)) for k, v in axes.items()))


def _parse_workload(entry, i: int) -> Workload | TraceTensors:
    """Normalize one ``workloads=`` entry; ValueError names the entry."""
    if isinstance(entry, TraceTensors):
        return entry
    if isinstance(entry, Workload):
        app, graph = entry.app, entry.graph
    elif isinstance(entry, str):
        if entry in ALL_APPS:
            app, graph = entry, None
        else:
            app, _, graph = entry.rpartition("-")
        if app not in ALL_APPS:
            raise ValueError(
                f"workloads[{i}]: unknown workload {entry!r} (want "
                f"'<app>' or '<app>-<graph>' with app in "
                f"{sorted(ALL_APPS)} and graph in "
                f"{GRAPH_INPUTS + GENERATED_GRAPHS})")
        entry = Workload(app, graph)
    elif isinstance(entry, (tuple, list)) and len(entry) == 2:
        app, graph = entry
        entry = Workload(app, graph)
    else:
        raise ValueError(
            f"workloads[{i}]: cannot interpret {entry!r} as a workload "
            f"(want a name, an (app, graph) pair, a workload() spec, or "
            f"prepared TraceTensors)")
    if app not in ALL_APPS:
        raise ValueError(f"workloads[{i}]: unknown app {app!r} "
                         f"(know {sorted(ALL_APPS)})")
    if ALL_APPS[app] and graph not in GRAPH_INPUTS + GENERATED_GRAPHS:
        raise ValueError(f"workloads[{i}]: app {app!r} needs a graph input "
                         f"from {GRAPH_INPUTS} or a generated graph from "
                         f"{GENERATED_GRAPHS}, got {graph!r}")
    if not ALL_APPS[app] and graph is not None:
        raise ValueError(f"workloads[{i}]: app {app!r} is a table workload; "
                         f"graph must be None, got {graph!r}")
    why = workload_key_error(app, graph, dict(entry.trace_kw))
    if why is not None:
        raise ValueError(f"workloads[{i}]: {why}")
    return entry


# ---------------------------------------------------------------------------
# Results container
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StudyPoint:
    """One evaluated (workload, hw-point, lazy-point) coordinate with its
    per-mechanism results."""

    workload: str
    hw_index: int
    lazy_index: int
    hw: HWParams
    lazy: LazyPIMConfig
    results: dict[str, SimResult]


_RATIO_KEYS = ("speedup", "traffic", "energy")


class ResultSet:
    """Tagged study results: one :class:`StudyPoint` per (workload,
    hw-point, lazy-point) coordinate, in workload-major order."""

    def __init__(self, points: Sequence[StudyPoint],
                 mechanisms: Sequence[str]):
        self.points = list(points)
        self.mechanisms = tuple(mechanisms)

    def __len__(self) -> int:
        return len(self.points)

    def __iter__(self):
        return iter(self.points)

    @classmethod
    def concat(cls, sets: Sequence["ResultSet"]) -> "ResultSet":
        """Concatenate result sets (e.g. the per-static-flag halves of a
        ``partial_commits`` ablation, which cannot share one study)."""
        points = [p for rs in sets for p in rs.points]
        mechanisms = tuple(dict.fromkeys(m for rs in sets
                                         for m in rs.mechanisms))
        return cls(points, mechanisms)

    def normalized(self, to: str = "cpu") -> list[dict[str, dict]]:
        """Per-point mechanism summaries normalized to the ``to`` baseline
        of the *same* point (the paper's CPU-only presentation): speedup /
        traffic / energy ratios plus the raw accumulators — one dict per
        point, aligned with ``self.points``."""
        for i, p in enumerate(self.points):
            # checked per point, not against the concat-unioned mechanisms
            # tuple: heterogeneous concatenated sets must fail loudly here
            if to not in p.results:
                raise ValueError(
                    f"normalized(to={to!r}) needs {to!r} in every point's "
                    f"mechanisms; points[{i}] ({p.workload}) only has "
                    f"{tuple(p.results)}")
        return [_engine.summarize(p.results, p.hw, to=to)
                for p in self.points]

    def to_rows(self) -> list[dict[str, Any]]:
        """Flat tabulation: one dict per (point, mechanism) with the
        coordinates, every ``SimResult`` field, the conflict rates, and —
        when the study ran a ``cpu`` baseline — the normalized ratios."""
        rows = []
        for p in self.points:
            norm = (_engine.summarize(p.results, p.hw)
                    if "cpu" in p.results else None)
            for m, r in p.results.items():
                row = dict(workload=p.workload, hw_index=p.hw_index,
                           lazy_index=p.lazy_index, mechanism=m)
                d = dataclasses.asdict(r)
                d.pop("name"), d.pop("mechanism")
                row.update(d)
                row["conflict_rate"] = r.conflict_rate
                row["conflict_rate_exact"] = r.conflict_rate_exact
                if norm is not None:
                    row.update({k: norm[m][k] for k in _RATIO_KEYS})
                rows.append(row)
        return rows

    def pivot(self, index: str | tuple[str, ...], columns: str,
              values: str) -> dict:
        """Spreadsheet pivot over :meth:`to_rows`:
        ``pivot("workload", "mechanism", "speedup")`` is the fig7 table.
        ``index`` may be a tuple of row fields (the key becomes a tuple);
        colliding cells raise rather than silently overwrite."""
        out: dict = {}
        for row in self.to_rows():
            ik = (row[index] if isinstance(index, str)
                  else tuple(row[k] for k in index))
            ck = row[columns]
            cell = out.setdefault(ik, {})
            if ck in cell:
                raise ValueError(
                    f"pivot({index!r}, {columns!r}): duplicate cell "
                    f"({ik!r}, {ck!r}) — add a distinguishing field to "
                    f"index")
            cell[ck] = row[values]
        return out

    def save_json(self, path: str | pathlib.Path) -> pathlib.Path:
        """Serialize the full result set (coordinates + hw/lazy configs +
        every SimResult field) — the golden-test artifact format."""
        payload = {
            "schema_version": RESULTSET_SCHEMA_VERSION,
            "mechanisms": list(self.mechanisms),
            "points": [{
                "workload": p.workload,
                "hw_index": p.hw_index,
                "lazy_index": p.lazy_index,
                "hw": dataclasses.asdict(p.hw),
                "lazy": dataclasses.asdict(p.lazy),
                "results": {m: dataclasses.asdict(r)
                            for m, r in p.results.items()},
            } for p in self.points],
        }
        path = pathlib.Path(path)
        path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
        return path

    @classmethod
    def load_json(cls, path: str | pathlib.Path) -> "ResultSet":
        """Load a :meth:`save_json` artifact.  A truncated, corrupt, or
        version-incompatible file raises :class:`ResultSetSchemaError`
        naming the path and the reason — never a raw ``JSONDecodeError`` /
        ``KeyError`` / ``TypeError`` that callers (golden tests, the serve
        layer's restart path) would have to guess at."""
        path = pathlib.Path(path)
        try:
            payload = json.loads(path.read_text())
        except json.JSONDecodeError as e:
            raise ResultSetSchemaError(
                f"{path}: not valid JSON (truncated or corrupt): {e}") \
                from e
        if not isinstance(payload, dict):
            raise ResultSetSchemaError(
                f"{path}: expected a JSON object, got "
                f"{type(payload).__name__}")
        # Pre-stamp artifacts (the committed goldens) carry no version
        # field; they are the version-1 layout, so a missing field loads.
        version = payload.get("schema_version", RESULTSET_SCHEMA_VERSION)
        if version != RESULTSET_SCHEMA_VERSION:
            raise ResultSetSchemaError(
                f"{path}: schema_version {version!r} unsupported (this "
                f"build reads version {RESULTSET_SCHEMA_VERSION})")
        try:
            points = [StudyPoint(
                workload=d["workload"], hw_index=d["hw_index"],
                lazy_index=d["lazy_index"], hw=HWParams(**d["hw"]),
                lazy=LazyPIMConfig(**d["lazy"]),
                results={m: SimResult(**r) for m, r in d["results"].items()},
            ) for d in payload["points"]]
            return cls(points, tuple(payload["mechanisms"]))
        except (KeyError, TypeError, AttributeError) as e:
            raise ResultSetSchemaError(
                f"{path}: malformed ResultSet payload "
                f"({type(e).__name__}: {e})") from e


@dataclasses.dataclass
class BucketLanes:
    """One geometry bucket's stacked execution unit, fully materialized:
    the pad-target ``shape`` (``pad_trace`` kwargs — also the compiled
    scan's geometry key), the study point indices riding this bucket
    (``lane_points``, in point order — lane ``i`` of the dispatch IS point
    ``lane_points[i]``), and the per-lane padded trace / hw / lazy triples
    ready for :func:`repro.sim.engine.stack_lanes`.  This is the
    currency the serve layer's cross-request coalescer trades in: lanes
    from different requests with equal ``shape`` (+ spec + static flags)
    stack into one dispatch and split back by lane slice."""

    shape: dict[str, int]
    lane_points: list[int]
    traces: list[TraceTensors]
    hws: list[HWParams]
    lazys: list[LazyPIMConfig]


# ---------------------------------------------------------------------------
# Execution plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StudyPlan:
    """The planner's predicted execution shape, computed before anything
    compiles or runs: geometry buckets (with their lane counts — workloads
    × hw points × lazy points folded onto the stacked axis) and the compile
    budget, at most one XLA compile per (mechanism, bucket)."""

    buckets: tuple[dict, ...]
    mechanisms: tuple[str, ...]
    num_points: int
    devices: int = 1

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def compiles_per_mechanism(self) -> dict[str, int]:
        """Predicted *cold-cache* compile count per mechanism: one per
        geometry bucket — independent of the device count, because each
        bucket compiles exactly once at its routed mesh size (the per-bucket
        ``devices`` entry) and ``engine.sweep_cache_sizes`` sums the
        single-device function with every mesh variant.  Warm jit caches can
        only lower the measured number (the cache-size deltas)."""
        return {m: self.num_buckets for m in self.mechanisms}

    @property
    def total_compiles(self) -> int:
        return len(self.mechanisms) * self.num_buckets

    def describe(self) -> str:
        lines = [f"{self.num_points} points x {len(self.mechanisms)} "
                 f"mechanisms in {self.num_buckets} geometry buckets "
                 f"(<= {self.total_compiles} XLA compiles)"]
        if self.devices > 1:
            lines[0] += f", lane mesh over {self.devices} devices"
        for b in self.buckets:
            lines.append(
                f"  bucket {b['num_lines']} lines x {b['num_windows']} "
                f"windows: {b['lanes']} lanes over {len(b['workloads'])} "
                f"workloads, pad overhead {b['line_pad_overhead']:.2f}x")
            if b.get("devices", 1) > 1:
                lines[-1] += (f", sharded {b['padded_lanes']} lanes / "
                              f"{b['devices']} devices")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# The study itself
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Study:
    """Declarative experiment spec — see the module docstring for the axis
    grammar.  Construction validates the spec; :meth:`plan` predicts the
    execution/compile shape; :meth:`run` executes through the bucketed
    stacked-dispatch engine (or the sequential reference with
    ``engine="sequential"``).

    In a profiler trace, :meth:`traces`, :meth:`bucket_lanes` and the
    batched :meth:`run` are the spans ``repro:traces``,
    ``repro:bucket_lanes`` and ``repro:run``, each with ``study`` set to
    :attr:`trace_id`; a call answered from the study's cache opens none."""

    workloads: Sequence
    hw: HWParams | HWGrid | Sequence[HWParams] | None = None
    mechanisms: Sequence[str] = _engine.MECHANISMS
    lazy: LazyPIMConfig | Sequence[LazyPIMConfig] | None = None
    threads: int = 16
    spec: SignatureSpec | None = None

    def __post_init__(self):
        if not self.workloads:
            raise ValueError("a study needs at least one workload")
        self._entries = [_parse_workload(e, i)
                         for i, e in enumerate(self.workloads)]
        self.mechanisms = tuple(self.mechanisms)
        for i, m in enumerate(self.mechanisms):
            if m not in _engine.MECHANISMS:
                raise ValueError(f"mechanisms[{i}]: unknown mechanism {m!r} "
                                 f"(know {_engine.MECHANISMS})")
        if not self.mechanisms:
            raise ValueError("a study needs at least one mechanism")
        if isinstance(self.hw, (HWParams, HWGrid)) or self.hw is None:
            self._hws, self._zipped = None, False
        else:
            self._hws = list(self.hw)
            self._zipped = True
            if len(self._hws) != len(self._entries):
                raise ValueError(
                    f"hw list length {len(self._hws)} != "
                    f"{len(self._entries)} workloads (an explicit hw list "
                    f"is zipped per-workload; use grid(...) for a "
                    f"cross-product)")
            for i, h in enumerate(self._hws):
                if not isinstance(h, HWParams):
                    raise ValueError(f"hw[{i}]: expected HWParams, got "
                                     f"{type(h).__name__}")
        lazys = ([self.lazy] if isinstance(self.lazy, LazyPIMConfig)
                 else [LazyPIMConfig()] if self.lazy is None
                 else list(self.lazy))
        if not lazys:
            raise ValueError("lazy list must not be empty")
        for i, c in enumerate(lazys):
            if not isinstance(c, LazyPIMConfig):
                raise ValueError(f"lazy[{i}]: expected LazyPIMConfig, got "
                                 f"{type(c).__name__}")
            for f in _engine._LAZY_STATIC_FIELDS:
                if getattr(c, f) != getattr(lazys[0], f):
                    raise ValueError(
                        f"lazy[{i}]: static flag {f}={getattr(c, f)!r} "
                        f"differs from lazy[0] ({getattr(lazys[0], f)!r}); "
                        f"static flags select a different compiled dataflow "
                        f"— run one study per static combo and "
                        f"ResultSet.concat the results")
        self._lazys = lazys
        self._tts: list[TraceTensors] | None = None
        self._bls: list[BucketLanes] | None = None
        # The ``study=`` value of this study's spans in a profiler trace.
        self.trace_id = next(_TRACE_IDS)

    # -- axis materialization ----------------------------------------------

    def traces(self) -> list[TraceTensors]:
        """Prepared TraceTensors of the workload axis (cached)."""
        if self._tts is None:
            tts = []
            with spans.span("traces", study=self.trace_id):
                for e in self._entries:
                    if isinstance(e, TraceTensors):
                        tts.append(e)
                        continue
                    trace = make_trace(e.app, e.graph,
                                       threads=e.threads or self.threads,
                                       **dict(e.trace_kw))
                    tts.append(prepare(trace, e.spec or self.spec))
            self._tts = tts
        return self._tts

    def hw_points(self) -> list[HWParams]:
        """The hw axis: grid points, the zipped per-workload list, or the
        single (possibly default) HWParams."""
        if self._zipped:
            return list(self._hws)
        if isinstance(self.hw, HWGrid):
            return self.hw.points()
        return [self.hw or HWParams()]

    def lazy_points(self) -> list[LazyPIMConfig]:
        return list(self._lazys)

    @property
    def num_points(self) -> int:
        """Total (workload, hw, lazy) points — computable without generating
        a single trace, so admission control (``repro.serve``) can bound a
        request's lane count before paying any synthesis or compile cost."""
        return len(self._lanes())

    def _lanes(self) -> list[tuple[int, int, int]]:
        """(workload, hw, lazy) index triples in point order: workload-major,
        then hw, then lazy.  A zipped hw list pins hw index == workload
        index instead of crossing."""
        W, L = len(self._entries), len(self._lazys)
        if self._zipped:
            return [(w, w, li) for w in range(W) for li in range(L)]
        H = len(self.hw_points())
        return [(w, h, li) for w in range(W) for h in range(H)
                for li in range(L)]

    # -- planning -----------------------------------------------------------

    def plan(self, devices: int | None = None) -> StudyPlan:
        """Predict the execution shape — geometry buckets, lane counts, and
        the compile budget — without dispatching anything.

        ``devices`` is the lane-mesh width :meth:`run` will shard over
        (``None`` = every visible device, matching ``run``'s default); each
        bucket routes to the largest pow2 device subset its lane count
        fills (the bucket's ``devices`` entry) and pads its lane axis up to
        ``padded_lanes``, the next mesh multiple.  The compile budget is
        device-count-independent — one compile per (mechanism, bucket),
        whichever mesh variant it lands in — so ``check_budget --live``
        asserts the same prediction at any simulated device count."""
        tts = self.traces()
        lanes = self._lanes()
        resolved = _mesh.resolve_devices(devices)
        buckets = []
        for idx, shape in bucket_shapes(tts):
            members = set(idx)
            sel = [lane for lane in lanes if lane[0] in members]
            real = sum(tts[w].num_lines for w, _, _ in sel)
            d = _mesh.devices_for(len(sel), resolved) if sel else 1
            buckets.append(dict(
                **shape,
                spec=dataclasses.asdict(tts[idx[0]].spec),
                workloads=[tts[i].name for i in idx],
                lanes=len(sel),
                devices=d,
                padded_lanes=_mesh.mesh_lane_width(len(sel), d) if sel else 0,
                line_pad_overhead=shape["num_lines"] * len(sel) / max(real, 1),
            ))
        return StudyPlan(buckets=tuple(buckets), mechanisms=self.mechanisms,
                         num_points=len(lanes), devices=resolved)

    # -- lane materialization ------------------------------------------------

    def bucket_lanes(self) -> list[BucketLanes]:
        """The batched execution units: one :class:`BucketLanes` per
        geometry bucket, each carrying its padded per-lane trace / hw /
        lazy triples in point order (cached — padding is paid once per
        study, however many times the serve layer re-dispatches it)."""
        if self._bls is None:
            tts, hws = self.traces(), self.hw_points()
            lazys, lanes = self.lazy_points(), self._lanes()
            out = []
            with spans.span("bucket_lanes", study=self.trace_id):
                for idx, shape in bucket_shapes(tts):
                    members = set(idx)
                    sel = [j for j, lane in enumerate(lanes)
                           if lane[0] in members]
                    if not sel:
                        continue
                    padded = {w: pad_trace(tts[w], **shape) for w in idx}
                    out.append(BucketLanes(
                        shape=shape, lane_points=sel,
                        traces=[padded[lanes[j][0]] for j in sel],
                        hws=[hws[lanes[j][1]] for j in sel],
                        lazys=[lazys[lanes[j][2]] for j in sel]))
            self._bls = out
        return self._bls

    def _finalize_lanes(self, lane_points: list[int],
                        accs: dict[str, dict]) -> list[StudyPoint]:
        """One bucket's host accumulators as finalized points, in lane
        order: lane ``pos`` of every ``accs`` array is study point
        ``lane_points[pos]``; pad lanes past them are never read.  Every
        lane passes the :func:`repro.core.mechanisms.finalize_result`
        integrity sentinel; a poisoned lane raises ``ResultIntegrityError``
        naming the workload, mechanism and field."""
        tts, hws, lazys = self.traces(), self.hw_points(), self.lazy_points()
        lanes = self._lanes()
        points = []
        for pos, j in enumerate(lane_points):
            w, h, li = lanes[j]
            res = {m: finalize_result(tts[w].name, m,
                                      {k: v[pos] for k, v in acc.items()})
                   for m, acc in accs.items()}
            points.append(StudyPoint(workload=tts[w].name, hw_index=h,
                                     lazy_index=li, hw=hws[h], lazy=lazys[li],
                                     results=res))
        return points

    def points_from_lane_accs(self, accs: dict[str, dict]) -> ResultSet:
        """Split stacked accumulators back into this study's tagged points:
        ``accs`` maps mechanism → host accumulator dict whose arrays carry a
        leading lane axis of length ``num_points``, ordered like the
        single bucket's ``lane_points``.  This is the result-splitting half
        of cross-request coalescing (:mod:`repro.serve.coalesce`): the
        server slices the group dispatch's lane axis per request and hands
        each request's slab here.  Only valid for single-bucket studies
        (the coalescer's admission rule), where lane order == point order."""
        bls = self.bucket_lanes()
        if len(bls) != 1:
            raise ValueError(
                f"points_from_lane_accs needs a single-bucket study, this "
                f"one has {len(bls)} buckets (serve such studies "
                f"uncoalesced)")
        return ResultSet(self._finalize_lanes(bls[0].lane_points, accs),
                         self.mechanisms)

    # -- execution ----------------------------------------------------------

    def run(self, engine: str = "batch", on_dispatch=None,
            devices: int | None = None) -> ResultSet:
        """Execute the study.

        ``engine="batch"`` (default) runs the planner: bucket, pad, fold
        every axis onto the stacked lane dimension, one dispatch per
        (mechanism, bucket).  ``engine="sequential"`` runs every point
        through the per-trace reference path (``repro.sim.engine.run_all``)
        — bit-exact with the planner on every field, and the differential
        anchor the cross-engine tests compare against.

        ``devices`` shards each bucket's stacked lane axis over a lane mesh
        (``None`` = every visible device; on a 1-device host that is the
        byte-identical single-device path).  Buckets route per
        :meth:`plan`: largest pow2 device subset their lanes fill, lane
        axis padded to the mesh multiple with all-sentinel masked lanes
        that contribute nothing.  Sharded results are bit-exact with
        ``devices=1`` on every ``SimResult`` field
        (``tests/test_mesh_dispatch.py``).  Batch engine only —
        ``engine="sequential"`` with ``devices > 1`` is a ``ValueError``
        (the sequential path is the single-device reference).

        ``on_dispatch`` is an optional per-dispatch boundary, called as
        ``on_dispatch(dispatch_info, thunk)`` once per compiled-scan
        execution (per (mechanism, bucket) in the batched engine, per
        (point, mechanism) in the sequential one) with a :class:`Dispatch`
        describing the unit and a zero-arg thunk that executes it.  The
        boundary must return the thunk's result unchanged or raise; raising
        cancels the study at that dispatch.  This is the hook the serve
        layer uses for deadline cancellation, heartbeats, retry-scoped
        error capture and fault injection.
        """
        if engine == "batch":
            return self._run_batched(on_dispatch, devices=devices)
        if engine == "sequential":
            if devices is not None and int(devices) != 1:
                raise ValueError(
                    f"engine='sequential' is the single-device reference "
                    f"path; devices={devices} only applies to "
                    f"engine='batch'")
            return self._run_sequential(on_dispatch)
        raise ValueError(f"unknown engine {engine!r} "
                         f"(want 'batch' or 'sequential')")

    def _run_sequential(self, on_dispatch=None) -> ResultSet:
        tts, hws, lazys = self.traces(), self.hw_points(), self.lazy_points()
        points = []
        for w, h, li in self._lanes():
            res = {}
            for m in self.mechanisms:
                def thunk(m=m, w=w, h=h, li=li):
                    return _engine.run_mechanism(tts[w], hws[h], m, lazys[li])
                if on_dispatch is None:
                    res[m] = thunk()
                else:
                    res[m] = on_dispatch(
                        Dispatch(engine="sequential", mechanism=m,
                                 workload=tts[w].name), thunk)
            points.append(StudyPoint(workload=tts[w].name, hw_index=h,
                                     lazy_index=li, hw=hws[h], lazy=lazys[li],
                                     results=res))
        return ResultSet(points, self.mechanisms)

    def _run_batched(self, on_dispatch=None,
                     devices: int | None = None) -> ResultSet:
        bls = self.bucket_lanes()
        resolved = _mesh.resolve_devices(devices)
        points: list[StudyPoint | None] = [None] * self.num_points
        with spans.span("run", study=self.trace_id):
            for bl in bls:
                n = len(bl.traces)
                d = _mesh.devices_for(n, resolved)
                with spans.span("stack"):
                    stacked, shw, scfg = self._stack_lanes(bl, n, d)
                boundary = None
                if on_dispatch is not None:
                    def boundary(m, thunk, _shape=bl.shape, _n=n, _d=d):
                        return on_dispatch(
                            Dispatch(engine="batch", mechanism=m, lanes=_n,
                                     bucket_lines=_shape["num_lines"],
                                     devices=_d), thunk)
                accs = _engine._sweep_accs(stacked, shw, self.mechanisms,
                                           scfg, boundary=boundary, devices=d)
                with spans.span("finalize"):
                    for j, p in zip(bl.lane_points,
                                    self._finalize_lanes(bl.lane_points,
                                                         accs)):
                        points[j] = p
        return ResultSet(points, self.mechanisms)

    def _stack_lanes(self, bl: BucketLanes, n: int, d: int):
        """One bucket's stacked (trace, hw, lazy) pytrees, its lane axis
        padded to the ``d``-device mesh multiple."""
        return _engine.stack_lanes(bl.traces, bl.hws, bl.lazys, bl.shape,
                                   _mesh.mesh_lane_width(n, d))
