"""Workload trace façade (paper §6.1–§6.2 + extended families).

The paper partitions each application into PIM kernels (memory-intensive,
cache-hostile) and processor threads (cache-friendly), then simulates their
concurrent execution in gem5.  We regenerate the same structure as *window
traces*: a sequence of partial-kernel windows (<=250 signature insertions
per set, §5.4); per window the cache-line addresses touched by the PIM
kernel and by the concurrently-running processor threads, plus instruction
counts, and a per-kernel pre-write line set for the inter-kernel processor
phase (the source of the *dirty conflicts* that dominate the CPUWriteSet —
§5.6: 95.4 % of insertions).

Synthesis itself is JAX-native (:mod:`repro.sim.synth`): every random value
is a Threefry-2x32 counter hash, so a whole trace is one jit-compiled
tensor program produced on-device.  ``make_trace(..., backend="ref")``
runs the sequential numpy reference (:mod:`repro.sim._traceref`) instead;
the two are bit-identical on every workload (``tests/test_trace_synth.py``).

Workload families and their access-pattern rationale:

* **Graph edgeMap** (``pagerank``/``radii``/``components`` × SNAP-shaped
  inputs, §6.1): sequential CSR edge-array reads + ``p_curr[neighbor]``
  gathers scattered through the power-law degree distribution (the
  pointer-chasing the paper targets); processor threads touch bookkeeping
  state, with a per-app rate of RAW-capable ``p_curr`` writes (§6.2).
* **HTAP IMDB** (``htap128/192/256``, §6.1): analytics scan tables
  sequentially + probe a hash-join area randomly; transactions touch a few
  tuples biased toward the scanned (hot) table — real-time analytics on
  fresh transactional data.
* **BFS/SSSP frontier kernels** (``bfs``/``sssp``, new): pull/relax sweeps
  whose per-level frontier rises and falls — *bursty, frontier-sized
  windows* (near-empty at the root/fringe, full at the peak level), with
  host-side relaxation assists as the RAW-capable writes.  Exercises the
  irregular-update patterns the PIM-adoption literature calls out (Ghose
  et al. 2018; Mutlu et al. 2020) beyond the paper's three Ligra kernels.
* **Streaming-ingest HTAP** (``htap_stream``, new): transactions *append*
  tuples at a moving tail; analytics scan the recently-ingested region a
  fixed lag behind it (§3.1's real-time-analytics case).  The hot tail
  makes the dirty-line class dominant — exactly the CPUWriteSet pressure
  PIM-DBI targets (§5.6) — and the reuse-heavy hot-tail reads are the
  worst case for NC.
* **Multi-tenant mix** (``mtmix``, new): two applications' kernels
  interleave over one shared PIM data region (shared CSR edges, private
  vertex arrays).  Both tenants' threads write every window, so the
  CPUWriteSet carries *cross-kernel* pressure: the inactive tenant's
  writes alias into the active kernel's PIMReadSet only through real H3
  false positives (§5.3/§5.6).

Each recorded CPU access stands for ``cpu_reuse`` dynamic accesses
(temporal locality within a window); all reported metrics are ratios,
invariant to the window subsampling factor (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.runtime import spans
from repro.sim import graphs as G
from repro.sim import synth
from repro.sim.synth import AR, AW, BR, BW, MAX_SIG_ADDRS  # noqa: F401  (re-export)
from repro.sim.synth import APP_CPU_WRITES  # noqa: F401  (re-export)

GRAPH_APPS = ("pagerank", "radii", "components")
GRAPH_INPUTS = ("enron", "arxiv", "gnutella")
# Graph inputs built from keys of their own (Graph500's Kronecker graph):
# accepted wherever a graph input is, but in no fleet of all_workloads().
GENERATED_GRAPHS = tuple(G.GENERATED_GRAPHS)
# make_trace's own keywords a workload spec may set
TRACE_KEYS = ("seed", "num_kernels", "windows_per_kernel", "scale",
              "cpu_reuse", "backend")
HTAP_APPS = ("htap128", "htap192", "htap256")
FRONTIER_APPS = ("bfs", "sssp")
STREAM_APPS = ("htap_stream",)
MT_APPS = ("mtmix",)
# Captured from live model execution (repro.capture), not synthesized:
# first-class workloads everywhere a synthetic app name is accepted
# (Study, serve admission), but build_plan rejects them —
# there is no synthesis plan to build.
CAPTURE_APPS = ("capture/kv_serve", "capture/moe_experts",
                "capture/lazy_embed")

# app -> needs a graph input?
ALL_APPS = {**{a: True for a in GRAPH_APPS + FRONTIER_APPS + MT_APPS},
            **{a: False for a in HTAP_APPS + STREAM_APPS + CAPTURE_APPS}}


@dataclasses.dataclass(frozen=True)
class WindowTrace:
    """Fixed-shape trace of W partial-kernel windows (numpy or device
    arrays — ``prepare`` accepts either)."""

    name: str
    threads: int
    num_lines: int           # PIM data region size in 64 B lines
    # PIM kernel accesses (line ids; -1 = empty slot)
    pim_reads: np.ndarray    # (W, AR) int32
    pim_writes: np.ndarray   # (W, AW) int32
    # Processor accesses to the PIM data region during the window
    cpu_reads: np.ndarray    # (W, BR) int32
    cpu_writes: np.ndarray   # (W, BW) int32
    # Kernel structure
    kernel_id: np.ndarray    # (W,) int32
    kernel_start: np.ndarray  # (W,) bool
    kernel_end: np.ndarray   # (W,) bool
    # Inter-kernel processor phase: lines written before each kernel begins
    pre_writes: np.ndarray   # (K, num_lines) bool
    # Work counts
    pim_instr: np.ndarray    # (W,) float32
    cpu_instr: np.ndarray    # (W,) float32
    cpu_priv_accesses: np.ndarray  # (W,) float32 (non-PIM-region accesses)
    cpu_priv_miss_rate: float
    cpu_reuse: float = 6.0

    @property
    def num_windows(self) -> int:
        return int(self.pim_reads.shape[0])

    @property
    def num_kernels(self) -> int:
        return int(self.pre_writes.shape[0])


def workload_key_error(app: str, graph_name: str | None,
                       keys) -> str | None:
    """Why a workload's trace keys are refused, or ``None``: keys beyond
    :data:`TRACE_KEYS` must be keys its graph input takes, and a generated
    graph's required keys must all be there."""
    known, required = G.graph_keys(graph_name) if ALL_APPS.get(app) else ((), ())
    unknown = sorted(set(keys) - set(TRACE_KEYS) - set(known))
    if unknown:
        return (f"unknown trace keys {unknown} for {app!r} on "
                f"{graph_name!r} (know {sorted(TRACE_KEYS + known)})")
    missing = sorted(set(required) - set(keys))
    if missing:
        return f"graph {graph_name!r} needs the keys {missing}"
    return None


def build_plan(
    app: str,
    graph_name: str | None = None,
    threads: int = 16,
    num_kernels: int = 24,
    windows_per_kernel: int = 3,
    seed: int = 0,
    scale: float | None = None,
    cpu_reuse: float | None = None,
    **graph_kw,
):
    """(plan, edges-or-None, display name) for any workload family, with
    the same per-family defaults ``make_trace`` applies (scale 0.01 for the
    table families, streaming's higher ``cpu_reuse``).  ``graph_kw`` goes to
    the graph input (a generated graph's keys); a table family or a
    SNAP-shaped graph given one raises ``TypeError``.  The public plan
    entry point for benchmarks that drive :mod:`repro.sim.synth` directly."""
    if app.startswith("capture/"):
        raise ValueError(
            f"{app!r} is a captured workload: it is recorded from live "
            f"model execution (repro.capture), not synthesized — use "
            f"make_trace")
    if app not in ALL_APPS:
        raise ValueError(f"unknown app {app!r} (know {sorted(ALL_APPS)})")
    if ALL_APPS[app] and graph_name not in GRAPH_INPUTS + GENERATED_GRAPHS:
        raise ValueError(
            f"{app!r} needs a graph input from {GRAPH_INPUTS} or a generated "
            f"graph from {GENERATED_GRAPHS}, got {graph_name!r}")
    if not ALL_APPS[app] and graph_name is not None:
        raise ValueError(f"{app!r} is a table workload: graph_name must be "
                         f"None, got {graph_name!r}")
    if not ALL_APPS[app] and graph_kw:
        raise TypeError(f"{app!r} takes no keys {sorted(graph_kw)}")
    if scale is None:
        scale = 0.01 if app in HTAP_APPS + STREAM_APPS else 1.0
    if cpu_reuse is None:
        cpu_reuse = 8.0 if app in STREAM_APPS else 6.0
    return _build(app, graph_name, threads, num_kernels, windows_per_kernel,
                  seed, scale, cpu_reuse, graph_kw)


def _build(app, graph_name, threads, num_kernels, wpk, seed, scale, cpu_reuse,
           graph_kw):
    if app in GRAPH_APPS:
        plan, edges = synth.build_graph_plan(
            app, graph_name, threads, num_kernels, wpk, seed, scale, cpu_reuse,
            **graph_kw)
        return plan, edges, f"{app}-{graph_name}"
    if app in FRONTIER_APPS:
        plan, edges = synth.build_frontier_plan(
            app, graph_name, threads, num_kernels, wpk, seed, scale, cpu_reuse,
            **graph_kw)
        return plan, edges, f"{app}-{graph_name}"
    if app in MT_APPS:
        plan, edges = synth.build_mt_plan(
            app, graph_name, threads, num_kernels, wpk, seed, scale, cpu_reuse,
            **graph_kw)
        return plan, edges, f"{app}-{graph_name}"
    if app in HTAP_APPS:
        plan = synth.build_htap_plan(
            app, threads, num_kernels, wpk, seed, scale, cpu_reuse)
        return plan, None, app
    if app in STREAM_APPS:
        plan = synth.build_stream_plan(
            app, threads, num_kernels, wpk, seed, scale, cpu_reuse)
        return plan, None, app
    raise ValueError(f"unknown app {app!r}")


def _assemble(plan, name: str, arrays: dict) -> WindowTrace:
    return WindowTrace(
        name=name, threads=plan.threads, num_lines=plan.total_lines,
        cpu_priv_miss_rate=plan.cpu_priv_miss_rate, cpu_reuse=plan.cpu_reuse,
        **arrays)


def make_trace(
    app: str,
    graph_name: str | None = None,
    threads: int = 16,
    seed: int = 0,
    num_kernels: int = 24,
    windows_per_kernel: int = 3,
    scale: float | None = None,
    cpu_reuse: float | None = None,
    backend: str = "jax",
    **graph_kw,
) -> WindowTrace:
    """Uniform entry point for every workload family.

    Graph-input families (graph/frontier/mtmix apps) need ``graph_name``;
    table families (HTAP/streaming) don't.  A generated graph
    (:data:`GENERATED_GRAPHS`) takes its keys as ``graph_kw``, e.g.
    ``make_trace("bfs", "kronecker", kron_scale=21, edge_factor=16,
    graph_seed=1)``; it is built once per process and ignores ``seed``,
    which still draws the trace on it (fresh search roots on one graph).  ``backend="jax"`` (default)
    runs the jit-compiled on-device generator; ``backend="ref"`` the
    sequential numpy reference — bit-identical by construction and by test.
    ``capture/*`` apps are *recorded* from live model execution
    (:mod:`repro.capture`) instead of synthesized; unknown ``capture/``
    specs raise the same admission-time ValueError unknown apps do.

    In a profiler trace this is the ``repro:synth`` span: plan build, graph
    generation (``repro:graph``, the first time a graph is named) and the
    synthesis dispatch (the device program runs on past it; the first host
    read in ``prepare`` waits for it).
    """
    with spans.span("synth"):
        if app.startswith("capture/"):
            if graph_name is not None:
                raise ValueError(f"{app!r} is a captured workload: "
                                 f"graph_name must be None, got "
                                 f"{graph_name!r}")
            if graph_kw:
                raise TypeError(f"{app!r} takes no keys {sorted(graph_kw)}")
            from repro import capture

            return capture.capture_trace(
                app, threads=threads, seed=seed, num_kernels=num_kernels,
                windows_per_kernel=windows_per_kernel, scale=scale,
                cpu_reuse=cpu_reuse, backend=backend)
        plan, edges, name = build_plan(app, graph_name, threads,
                                       num_kernels, windows_per_kernel, seed,
                                       scale, cpu_reuse, **graph_kw)
        if backend == "jax":
            arrays = synth.synthesize(plan, seed, edges)
        elif backend == "ref":
            from repro.sim import _traceref

            arrays = _traceref.synthesize_ref(plan, seed, edges)
        else:
            raise ValueError(f"unknown backend {backend!r}")
        return _assemble(plan, name, arrays)


def make_graph_trace(app, graph_name, threads=16, num_kernels=24,
                     windows_per_kernel=3, seed=0, scale=1.0, cpu_reuse=6.0,
                     backend="jax") -> WindowTrace:
    """Trace for a Ligra graph app (see module docstring for the shapes)."""
    assert app in GRAPH_APPS, app
    return make_trace(app, graph_name, threads=threads, seed=seed,
                      num_kernels=num_kernels,
                      windows_per_kernel=windows_per_kernel, scale=scale,
                      cpu_reuse=cpu_reuse, backend=backend)


def make_htap_trace(app="htap128", threads=16, num_kernels=24,
                    windows_per_kernel=3, seed=0, scale=0.01, cpu_reuse=6.0,
                    backend="jax") -> WindowTrace:
    """Trace for the HTAP IMDB (§6.1)."""
    assert app in HTAP_APPS, app
    return make_trace(app, None, threads=threads, seed=seed,
                      num_kernels=num_kernels,
                      windows_per_kernel=windows_per_kernel, scale=scale,
                      cpu_reuse=cpu_reuse, backend=backend)


def all_workloads(extended: bool = False,
                  captured: bool = False) -> list[tuple[str, str | None]]:
    """The paper's 12 evaluated (app, input) pairs (Fig. 7); with
    ``extended=True``, also the new families (frontier kernels on every
    graph input, streaming-ingest HTAP, multi-tenant mixes); with
    ``captured=True``, also the live-model captured families
    (:mod:`repro.capture`) — opt-in, so fig7-style fleets keep the
    paper-set means unchanged by default."""
    out: list[tuple[str, str | None]] = [
        (a, g) for a in GRAPH_APPS for g in GRAPH_INPUTS
    ]
    out += [(a, None) for a in HTAP_APPS]
    if extended:
        out += [(a, g) for a in FRONTIER_APPS for g in GRAPH_INPUTS]
        out += [(a, None) for a in STREAM_APPS]
        out += [(a, g) for a in MT_APPS for g in GRAPH_INPUTS]
    if captured:
        out += [(a, None) for a in CAPTURE_APPS]
    return out
