"""Execution engines behind the declarative ``Study`` planner.

The one front door for experiments is :class:`repro.sim.study.Study`
(re-exported as ``repro.api``): a declarative (workloads × hw × mechanisms ×
lazy-config) spec whose ``run()`` plans execution automatically.  This
module provides the layered engines the planner dispatches through — kept
public because they are also the differential references that pin the
planner bit-exact:

* **Sequential reference** — :func:`run_all` / :func:`run_mechanism` run one
  prepared trace through each mechanism's own jitted scan
  (``neutral_trace`` keys the jit cache on geometry, not workload name).
  This is the readable per-point path every batched engine is tested
  against, field-for-field.
* **Stacked dispatch** — :func:`run_sweep` executes a *pre-stacked* sweep:
  every tensor leaf of the trace / hardware / lazy-config pytrees carries a
  leading point axis (:func:`stack_traces` / :func:`stack_hw` /
  :func:`stack_lazy`), and one jitted+vmapped scan per mechanism
  (:func:`_sweep_fn`, lru-cached — its jit cache size IS the measured
  compile count, :func:`sweep_cache_sizes`) runs all points in one
  execution.  ``HWParams`` leaves and ``LazyPIMConfig``'s numeric knobs are
  traced, so any values ride one compile; only trace geometry,
  ``SignatureSpec`` and the static lazy flags (``partial_commits``,
  ``cpuws_regs``, ``max_rollbacks``) select a different compiled function.
* **Bucketed fleet** — :func:`run_batch` is the planner's fleet form: a
  mixed-geometry workload list is grouped into pow2-ish geometry buckets
  (:func:`repro.sim.prep.bucket_traces`), padded under explicit validity
  masks, and dispatched through the stacked engine — one XLA compile per
  (mechanism, bucket) for any fleet size, bit-exact with sequential
  :func:`run_all` on every ``SimResult`` field.  ``run_batch`` itself is a
  thin wrapper over the ``Study`` planner, so the long-standing
  differential/golden tests (``tests/test_batch_engine.py``,
  ``tests/golden/fig7_batched_golden.json``) pin the planner's numerics.

The planner composes the axes by *folding them into the stacked workload
axis*: an hw grid or lazy ablation repeats each padded trace per
(hw-point, lazy-point) lane, so the whole cross-product still costs at most
one compile per (mechanism, bucket, static-flag combo) —
:meth:`repro.sim.study.Study.plan` predicts that budget before anything
runs, and ``benchmarks/check_budget.py --live`` cross-checks the prediction
against the measured :func:`sweep_cache_sizes` deltas.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coherence import LazyPIMConfig, _lazypim_acc, simulate_lazypim
from repro.core.mechanisms import (
    ACC_FNS,
    SimResult,
    _finalize,
    simulate_cg,
    simulate_cpu_only,
    simulate_fg,
    simulate_ideal,
    simulate_nc,
)
from repro.core.signatures import SignatureSpec
from repro.sim.costmodel import HWParams, hw_leaf_dtypes
from repro.sim.prep import (
    TRACE_DATA_FIELDS,
    TraceTensors,
    neutral_trace,
    prepare,
)
from repro.sim.trace import make_trace

MECHANISMS = ("cpu", "fg", "cg", "nc", "lazypim", "ideal")

_SIMULATORS = {
    "cpu": simulate_cpu_only,
    "ideal": simulate_ideal,
    "fg": simulate_fg,
    "cg": simulate_cg,
    "nc": simulate_nc,
}


def run_mechanism(
    tt: TraceTensors,
    hw: HWParams,
    mechanism: str,
    lazy_cfg: LazyPIMConfig | None = None,
) -> SimResult:
    if mechanism == "lazypim":
        return simulate_lazypim(tt, hw, lazy_cfg)
    return _SIMULATORS[mechanism](tt, hw)


def run_all(
    tt: TraceTensors,
    hw: HWParams | None = None,
    mechanisms: tuple[str, ...] = MECHANISMS,
    lazy_cfg: LazyPIMConfig | None = None,
) -> dict[str, SimResult]:
    hw = hw or HWParams()
    return {m: run_mechanism(tt, hw, m, lazy_cfg) for m in mechanisms}


# ---------------------------------------------------------------------------
# Pytree stacking: the leading point axis of the stacked dispatch engine
# ---------------------------------------------------------------------------


def stack_hw(hws: list[HWParams]) -> HWParams:
    """Stack a list of HWParams into one pytree with (S,)-shaped leaves.

    Leaf dtypes come from the explicit declaration
    :func:`repro.sim.costmodel.hw_leaf_dtypes` (int32 counts/capacities,
    float32 everything else), so sweeps that write ``offchip_bw_gbs=16``
    and ``offchip_bw_gbs=16.0`` hit the same compiled function.  Every
    field round-trips at its declared dtype (``tests/test_study.py``)."""
    dtypes = hw_leaf_dtypes()
    kw = {}
    for f in dataclasses.fields(HWParams):
        kw[f.name] = jnp.asarray(np.asarray(
            [getattr(h, f.name) for h in hws],
            dtype=np.dtype(dtypes[f.name])))
    return HWParams(**kw)


_LAZY_DATA_DTYPES = {
    "use_dbi": jnp.bool_,
    "dbi_interval_cycles": jnp.float32,
    "dbi_lines_per_fire": jnp.int32,
    "commit_exposure": jnp.float32,
}
_LAZY_STATIC_FIELDS = ("partial_commits", "cpuws_regs", "max_rollbacks")


def stack_lazy(cfgs: list[LazyPIMConfig]) -> LazyPIMConfig:
    """Stack LazyPIMConfigs into one pytree with (S,)-shaped numeric leaves.

    Only the traced knobs may vary: the static flags (``partial_commits``,
    ``cpuws_regs``, ``max_rollbacks``) select a different compiled dataflow,
    so a stack mixing them is rejected with a ``ValueError`` naming the
    offending entry — run one study/sweep per static-flag combo instead.
    """
    c0 = cfgs[0]
    for i, c in enumerate(cfgs[1:], start=1):
        for f in _LAZY_STATIC_FIELDS:
            if getattr(c, f) != getattr(c0, f):
                raise ValueError(
                    f"lazy config [{i}] has static {f}={getattr(c, f)!r} != "
                    f"{getattr(c0, f)!r} of config [0]: static flags select "
                    f"a different compiled dataflow and cannot share one "
                    f"stacked sweep")
    kw = {f: getattr(c0, f) for f in _LAZY_STATIC_FIELDS}
    for name, dt in _LAZY_DATA_DTYPES.items():
        kw[name] = jnp.asarray(np.asarray(
            [getattr(c, name) for c in cfgs], dtype=np.dtype(dt)))
    return LazyPIMConfig(**kw)


def stack_traces(tts: list[TraceTensors]) -> TraceTensors:
    """Stack same-geometry TraceTensors into one pytree with a leading sweep
    axis on every tensor leaf.

    All traces must share geometry metadata (line/window/kernel counts,
    access-slot widths and signature spec — they select the compiled
    shapes); raw mismatched-geometry stacks are rejected with a
    ``ValueError`` — route mixed fleets through :func:`run_batch` or a
    ``Study``, whose bucketing layer (:func:`repro.sim.prep.bucket_traces`)
    pads them onto shared bucket shapes first.  ``name``/``threads`` are
    taken from the first trace; the locality constants (``cpu_reuse``,
    ``cpu_priv_miss_rate``) are traced scalar leaves and stack per point
    like every other tensor.
    """
    t0 = tts[0]
    for t in tts[1:]:
        same = (t.num_lines == t0.num_lines and t.num_windows == t0.num_windows
                and t.num_kernels == t0.num_kernels and t.spec == t0.spec
                and all(getattr(t, k).shape == getattr(t0, k).shape
                        for k in ("pim_reads", "pim_writes",
                                  "cpu_reads", "cpu_writes")))
        if not same:
            raise ValueError(f"cannot stack {t.name}: geometry differs from "
                             f"{t0.name} (run_batch buckets mixed fleets)")
    fields = {f.name: getattr(t0, f.name) for f in dataclasses.fields(t0)}
    for key in TRACE_DATA_FIELDS:
        # Host-side stack + one device put per field: jnp.stack on a list
        # of device arrays issues expand_dims+concatenate per *element*,
        # whose dispatch overhead dominates wide (coalesced) stacks.
        fields[key] = jnp.asarray(
            np.stack([np.asarray(getattr(t, key)) for t in tts]))
    return TraceTensors(**fields)


# ---------------------------------------------------------------------------
# Stacked dispatch: one jitted+vmapped scan per mechanism
# ---------------------------------------------------------------------------


# Lanes × lines one scan step may carry.  Past it, vmapping more lanes
# into a step stops paying: at the 1 Mi-line bucket on a TPU v5e, the
# LazyPIM scan with 8 lanes vmapped ran in 3.69 s after a 111 s compile,
# one lane per step in 2.38 s after 12.9 s, bit-identical.
_STEP_LINE_LANES = 1 << 18


def lanes_per_step(lanes: int, num_lines: int) -> int:
    """Lanes vmapped into one scan step: the largest divisor of ``lanes``
    whose lanes × ``num_lines`` stay within ``_STEP_LINE_LANES`` (at least
    one), so every step has the same width."""
    cap = max(1, _STEP_LINE_LANES // num_lines)
    return max(d for d in range(1, min(lanes, cap) + 1) if lanes % d == 0)


def _lane_fn(mechanism: str):
    """The mechanism's window scan over a stacked lane axis: one vmap when
    every lane fits one step (:func:`lanes_per_step`), else ``lax.map``
    over equal groups of lanes, each group vmapped.  Lanes never interact,
    so the grouping changes no result."""
    acc = _lazypim_acc if mechanism == "lazypim" else ACC_FNS[mechanism]
    vm = jax.vmap(acc)

    def run(tt, *rest):
        lanes = tt.window_valid.shape[0]
        step = lanes_per_step(lanes, tt.num_lines)
        if step == lanes:
            return vm(tt, *rest)
        return jax.lax.map(lambda a: acc(*a), (tt, *rest), batch_size=step)

    return run


@functools.lru_cache(maxsize=None)
def _sweep_fn(mechanism: str):
    """One jitted window-scan over stacked lanes per mechanism (cached).
    The jit cache size of the returned function IS the sweep compile count.
    The LazyPIM config is mapped like the trace/hardware pytrees (its
    numeric leaves arrive stacked from :func:`stack_lazy`), so a
    lazy-ablation axis rides the same stacked dispatch as an hw sweep."""
    return jax.jit(_lane_fn(mechanism))


# Device counts > 1 whose mesh sweep variants have been built in this
# process.  NOT cleared with the jit caches: ``sweep_cache_sizes`` must keep
# counting a variant's compiles across ``_sweep_fn_sharded.cache_clear()``
# (re-creating an entry costs nothing and reads as size 0, same as
# ``_sweep_fn``).  Device counts are fixed per process, so every recorded
# count stays constructible.
_MESH_DEVICE_COUNTS: set[int] = set()


@functools.lru_cache(maxsize=None)
def _sweep_fn_sharded(mechanism: str, devices: int):
    """One jitted, shard_map-over-lanes-wrapped window-scan per
    (mechanism, device count) — the mesh sibling of :func:`_sweep_fn`,
    with its own jit cache: one compile key space per device count, which
    is exactly what ``Study.plan(devices=...)`` predicts."""
    from repro.sim.mesh import shard_lanes

    _MESH_DEVICE_COUNTS.add(devices)
    return jax.jit(shard_lanes(_lane_fn(mechanism), devices))


def _sweep_fn_mesh(mechanism: str, devices: int = 1):
    """The dispatch-function selector every mesh-aware caller goes
    through.  ``devices <= 1`` delegates to :func:`_sweep_fn` — THE
    current single-device function object, not a cached snapshot, so the
    byte-identical fallback also respects ``_sweep_fn.cache_clear()``
    (the tests' process-death simulation).  ``devices > 1`` returns the
    cached sharded variant."""
    if devices <= 1:
        return _sweep_fn(mechanism)
    return _sweep_fn_sharded(mechanism, devices)


def sweep_cache_sizes(mechanisms: tuple[str, ...] = MECHANISMS) -> dict[str, int]:
    """Measured XLA compile count per mechanism's sweep function (0 if the
    sweep function has never run), summed over the single-device function
    and every mesh variant built in this process.  Every batched engine —
    ``run_sweep``, ``run_batch``, the ``Study`` planner, sharded or not —
    executes through these functions, so the delta of these counts across a
    run is that run's measured compile cost (cross-checked against
    ``Study.plan()`` by ``benchmarks/check_budget.py --live``)."""
    return {m: _sweep_fn(m)._cache_size()
            + sum(_sweep_fn_sharded(m, d)._cache_size()
                  for d in sorted(_MESH_DEVICE_COUNTS))
            for m in mechanisms}


def sequential_cache_sizes(
    mechanisms: tuple[str, ...] = MECHANISMS,
) -> dict[str, int]:
    """Measured XLA compile count of the *sequential* per-trace jits behind
    :func:`run_all` (one entry per distinct geometry since
    ``neutral_trace``; one per workload before it)."""
    from repro.core import coherence as _coh
    from repro.core import mechanisms as _mech

    jits = {"cpu": _mech._run_cpu_only, "ideal": _mech._run_ideal,
            "fg": _mech._run_fg, "cg": _mech._run_cg, "nc": _mech._run_nc,
            "lazypim": _coh._run_lazypim}
    return {m: jits[m]._cache_size() for m in mechanisms}


def _sweep_accs(
    stt: TraceTensors,
    shw: HWParams,
    mechanisms: tuple[str, ...],
    scfg: LazyPIMConfig,
    boundary=None,
    devices: int = 1,
) -> dict[str, dict]:
    """Dispatch one stacked execution per mechanism; return host-side
    accumulator dicts with a leading point axis.  THE shared dispatch of
    every batched engine: ``run_sweep`` finalizes its output per point, the
    ``Study`` planner per (bucket, lane).

    ``boundary`` is the per-dispatch error/cancellation boundary: a callable
    ``(mechanism, thunk) -> accs`` invoked once per mechanism with a
    zero-arg thunk that runs the dispatch *and* materializes its results on
    the host (so device-side failures surface inside the boundary, not
    later).  A boundary must return the thunk's result unchanged or raise —
    it can time out, retry, or abort a dispatch, never alter numbers.  The
    serve layer (:mod:`repro.serve`) threads deadline checks, heartbeats and
    fault injection through here.

    ``devices`` selects the mesh variant: the stacked lane axis shards over
    a ``devices``-wide lane mesh (the lane count must already be a multiple
    of ``devices`` — the planner pads with :func:`repro.sim.prep.dummy_trace`
    lanes).  ``devices=1`` is the byte-identical single-device path.
    """
    out = {}
    for m in mechanisms:
        fn = _sweep_fn_mesh(m, devices)

        def thunk(m=m, fn=fn):
            acc = fn(stt, shw, scfg) if m == "lazypim" else fn(stt, shw)
            return {k: jax.device_get(v) for k, v in acc.items()}

        out[m] = thunk() if boundary is None else boundary(m, thunk)
    return out


def run_sweep(
    tt: TraceTensors,
    hw: HWParams,
    mechanisms: tuple[str, ...] = MECHANISMS,
    lazy_cfg: LazyPIMConfig | None = None,
) -> list[dict[str, SimResult]]:
    """Run every mechanism over a stacked sweep in one batched execution.

    ``tt``/``hw`` carry a leading sweep axis S on every tensor leaf (from
    :func:`stack_traces` / :func:`stack_hw`; a single trace can be tiled via
    ``stack_traces([tt] * S)``).  ``lazy_cfg`` is one config applied to
    every point (its leaves are broadcast onto the sweep axis; pass a
    per-point lazy axis through a ``Study`` instead).  Returns one
    ``{mechanism: SimResult}`` dict per sweep point — the same values,
    bit-for-bit, as S sequential :func:`run_all` calls (differentially
    tested), but compiled once per mechanism regardless of S.
    """
    if not mechanisms:
        return []
    lazy_cfg = lazy_cfg or LazyPIMConfig()
    num_points = jax.tree_util.tree_leaves(hw)[0].shape[0]
    ntt = neutral_trace(tt)  # jit keys on geometry, not the workload name
    scfg = stack_lazy([lazy_cfg] * num_points)
    accs = _sweep_accs(ntt, hw, mechanisms, scfg)
    points: list[dict[str, SimResult]] = []
    for i in range(num_points):
        points.append({
            m: _finalize(tt, m, {k: v[i] for k, v in acc.items()})
            for m, acc in accs.items()
        })
    return points


# ---------------------------------------------------------------------------
# Geometry-bucketed fleet batch engine (a thin wrapper over the planner)
# ---------------------------------------------------------------------------


def run_batch(
    tts: list[TraceTensors],
    hw: HWParams | list[HWParams] | None = None,
    mechanisms: tuple[str, ...] = MECHANISMS,
    lazy_cfg: LazyPIMConfig | None = None,
) -> list[dict[str, SimResult]]:
    """Run a whole workload fleet with one compiled scan per (mechanism,
    geometry bucket).

    Thin wrapper over the ``Study`` planner (:mod:`repro.sim.study`): the
    fleet becomes a study over prepared traces, ``hw`` one HWParams applied
    fleet-wide or a list aligned with ``tts`` (one per workload — the hook
    that composes an hw axis with the workload axis), and results come back
    per input workload, in input order — bit-exact with sequential
    :func:`run_all` on every ``SimResult`` field (differentially tested in
    ``tests/test_batch_engine.py``), at most ``len(mechanisms) ×
    num_buckets`` measured compiles for any fleet size.
    """
    from repro.sim.study import Study

    if not tts:
        return []
    if hw is not None and not isinstance(hw, HWParams):
        hw = list(hw)
        if len(hw) != len(tts):
            raise ValueError(f"hw list length {len(hw)} != fleet size {len(tts)}")
    study = Study(workloads=tts, hw=hw, mechanisms=mechanisms, lazy=lazy_cfg)
    return [p.results for p in study.run().points]


def summarize(results: dict[str, SimResult], hw: HWParams,
              to: str = "cpu") -> dict[str, dict]:
    """Normalize every mechanism to a baseline (the paper normalizes to
    CPU-only).  ``ResultSet.normalized`` applies this per study point."""
    base = results[to]
    base_e = base.energy_pj(hw)["total"]
    out = {}
    for m, r in results.items():
        out[m] = dict(
            speedup=base.time_ns / r.time_ns,
            traffic=r.offchip_bytes / base.offchip_bytes,
            energy=r.energy_pj(hw)["total"] / base_e,
            time_ns=r.time_ns,
            offchip_bytes=r.offchip_bytes,
            energy_pj=r.energy_pj(hw)["total"],
            conflict_rate=r.conflict_rate,
            conflict_rate_exact=r.conflict_rate_exact,
            flush_lines=r.flush_lines,
            blocked_accesses=r.blocked_accesses,
        )
    return out


def run_workload(
    app: str,
    graph_name: str | None = None,
    threads: int = 16,
    hw: HWParams | None = None,
    spec: SignatureSpec | None = None,
    mechanisms: tuple[str, ...] = MECHANISMS,
    lazy_cfg: LazyPIMConfig | None = None,
    **trace_kw,
) -> dict[str, SimResult]:
    """Convenience: trace -> prepare -> run_all (any workload family —
    seed graph/HTAP or the extended frontier/streaming/multi-tenant apps).

    With ``spec=None``, ``prepare`` applies the shared
    :func:`repro.core.signatures.default_spec` singleton — one set of
    byte-sliced H3 tables, one jit cache entry per mechanism — instead of
    re-deriving the hash family per call."""
    trace = make_trace(app, graph_name, threads=threads, **trace_kw)
    tt = prepare(trace, spec)
    return run_all(tt, hw or HWParams(), mechanisms, lazy_cfg)
