"""Packed-word simulator core + single-compile sweep microbenchmarks.

Measurements, written to ``BENCH_engine.json`` at the repo root:

0. **Geometry-bucketed batch engine** (``batch_engine``) — the full
   extended fig7 fleet through three engines with measured compile counts:
   the pre-batching per-workload-jit path (one compile per workload ×
   mechanism), the sequential geometry-keyed path, and the ``Study``
   planner (one compile per (mechanism, bucket), ≤
   ``FLEET_COMPILE_BUDGET``, cross-checked against ``Study.plan()``).

1. **Per-mechanism steady state** — windows/sec of every mechanism's window
   scan on the packed uint32-word path (``repro.core.mechanisms`` /
   ``repro.core.coherence``) vs the boolean seed path
   (``repro.core._boolref``), same traced-HWParams jit discipline on both
   sides, compile excluded (min over samples after a warm call).
2. **End-to-end fig7 wall time** — the full extended 22-workload ×
   6-mechanism speedup matrix (``benchmarks.fig7_speedup.run``) vs the
   same matrix on the boolean path, including trace generation, prepare,
   and compiles (key ``fig7_end_to_end_extended``; PR 2's
   ``fig7_end_to_end`` was the 12-workload paper set).
3. **Single-compile sweep** — a ``SWEEP_POINTS``-point off-chip-bandwidth
   hw-grid ``Study`` with the XLA compile count *measured* (jit cache size
   per mechanism) against the seed-style alternative: HWParams as a
   ``static_argnums`` jit argument, which recompiles every point.
4. **Trace-synthesis throughput** — the jit-compiled on-device generators
   (``repro.sim.synth``) vs the sequential numpy reference
   (``repro.sim._traceref``), per workload family, compile excluded, plus
   a >=1M-line large instance demonstrating on-device feasibility.

Usage: PYTHONPATH=src python -m benchmarks.run --bench engine
"""

from __future__ import annotations

import time

import jax

from benchmarks.timing import write_bench_json
from repro.core import _boolref
from repro.core.coherence import LazyPIMConfig, _lazypim_acc
from repro.core.mechanisms import ACC_FNS
from repro.sim import _traceref, engine, mesh, synth
from repro.sim.costmodel import HWParams
from repro.sim.engine import (
    run_all,
    sequential_cache_sizes,
    summarize,
    sweep_cache_sizes,
)
from repro.sim.prep import bucket_bound, pad_trace, prepare
from repro.sim.study import Study, grid, workload
from repro.sim.trace import all_workloads, build_plan, make_trace

from benchmarks.check_budget import FLEET_COMPILE_BUDGET  # single source

STEADY_WORKLOADS = (("pagerank", "arxiv"), ("htap128", None))
SWEEP_POINTS = 4
SAMPLES = 5

# htap128-large: 1,010,524 trace lines (the 1 Mi-line bucket), the largest
# instance the repo runs — more kernels × wider windows, the regime the
# on-device generator exists for, and big enough that per-device scan work
# dominates shard_map dispatch cost.
LARGE_WORKLOAD_KW = dict(scale=0.06, num_kernels=24, windows_per_kernel=16)
LARGE_LANES = 8

# Trace-synthesis throughput cases: one per family plus the large instance
# (the numpy reference loops over every window).
SYNTH_CASES = (
    ("pagerank", "enron", {}),
    ("htap256", None, {}),
    ("bfs", "enron", {}),
    ("htap_stream", None, {}),
    ("mtmix", "enron", {}),
    ("htap128", None, dict(LARGE_WORKLOAD_KW, label="htap128-large")),
)


def large_study(lanes: int = LARGE_LANES) -> Study:
    """htap128-large over a ``lanes``-point off-chip-bandwidth grid, every
    mechanism: one 1 Mi-line bucket with ``lanes`` stacked lanes."""
    return Study(
        workloads=[workload("htap128", **LARGE_WORKLOAD_KW)],
        hw=grid(offchip_bw_gbs=[float(16 * (i + 1)) for i in range(lanes)]))


def _steady_seconds(fn, *args) -> float:
    """Min-of-samples steady-state seconds per call, compile + one warm call
    excluded (the runners return dict pytrees, so block the whole tree)."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(SAMPLES):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_mechanisms(hw: HWParams, cfg: LazyPIMConfig) -> dict:
    packed = dict(ACC_FNS, lazypim=_lazypim_acc)
    boolean = dict(_boolref.ACC_FNS_BOOL, lazypim=_boolref._lazypim_acc_bool)
    out = {}
    for app, g in STEADY_WORKLOADS:
        tt = prepare(make_trace(app, g, threads=16))
        rows = {}
        for mech in ("cpu", "fg", "cg", "nc", "lazypim", "ideal"):
            args = (tt, hw, cfg) if mech == "lazypim" else (tt, hw)
            t_p = _steady_seconds(jax.jit(packed[mech]), *args)
            t_b = _steady_seconds(jax.jit(boolean[mech]), *args)
            rows[mech] = {
                "packed_ms": t_p * 1e3,
                "bool_ms": t_b * 1e3,
                "packed_windows_per_sec": tt.num_windows / t_p,
                "bool_windows_per_sec": tt.num_windows / t_b,
                "speedup": t_b / t_p,
            }
        out[tt.name] = {"num_lines": tt.num_lines,
                        "num_windows": tt.num_windows,
                        "mechanisms": rows}
    return out


def bench_fig7_wall(hw: HWParams) -> dict:
    """Full extended fig7 matrix (22 workloads × 6 mechanisms, incl. trace
    generation, prepare and compiles) — the packed path (now the bucketed
    batch engine via ``fig7_speedup.run``) vs the boolean seed path.
    NOTE: recorded under ``fig7_end_to_end_extended`` — PR 2's
    ``fig7_end_to_end`` measured the 12-workload paper set, a different
    quantity (the extended matrix adds ~3 trace geometries of scan
    recompiles), so the key changed to keep committed records comparable."""
    from benchmarks import fig7_speedup

    t0 = time.perf_counter()
    fig7_speedup.run(extended=True)
    packed_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for app, g in all_workloads(extended=True):
        tt = prepare(make_trace(app, g, threads=16))
        summarize(_boolref.run_all_bool(tt, hw), hw)
    bool_s = time.perf_counter() - t0
    return {"workloads": len(all_workloads(extended=True)),
            "packed_s": packed_s, "bool_s": bool_s,
            "speedup": bool_s / packed_s,
            "note": "packed side runs the bucketed batch engine with scan "
                    "compiles warm from the batch_engine section (which "
                    "records the cold-compile walls)"}


def bench_sweep(hw: HWParams, cfg: LazyPIMConfig) -> dict:
    bws = [16.0 * (i + 1) for i in range(SWEEP_POINTS)]
    study = Study(workloads=[workload("pagerank", "arxiv")],
                  hw=grid(offchip_bw_gbs=bws), lazy=cfg)
    # Materialize trace prep outside the timed region.  The static-argnums
    # comparison below runs on the SAME padded bucket geometry the planner
    # dispatches (pagerank-arxiv padded to its pow4 bound), so the walls
    # compare one compile vs four compiles of one identical scan — not
    # padded-vs-unpadded shapes.
    tt = study.traces()[0]
    ptt = pad_trace(tt, num_lines=bucket_bound(tt.num_lines))
    before = engine.sweep_cache_sizes()
    t0 = time.perf_counter()
    study.run()
    sweep_wall = time.perf_counter() - t0
    after = engine.sweep_cache_sizes()
    sweep_compiles = {m: after[m] - before[m] for m in after}

    # Seed-style: HWParams as a static jit argument — one XLA compile per
    # distinct hw point per mechanism.  Compiles are counted by a trace-time
    # side effect (the Python body only runs when jit misses), which is
    # immune to jax's shared-by-function pjit cache.
    static_compiles = {m: 0 for m in list(ACC_FNS) + ["lazypim"]}

    def counted(fn, m):
        def g(*args):
            static_compiles[m] += 1
            return fn(*args)
        return g

    static_fns = {m: jax.jit(counted(fn, m), static_argnums=(1,))
                  for m, fn in ACC_FNS.items()}
    static_fns["lazypim"] = jax.jit(counted(_lazypim_acc, "lazypim"),
                                    static_argnums=(1, 2))
    t0 = time.perf_counter()
    for b in bws:
        hw_b = HWParams(offchip_bw_gbs=b)
        for m, fn in static_fns.items():
            args = (ptt, hw_b, cfg) if m == "lazypim" else (ptt, hw_b)
            jax.block_until_ready(fn(*args))
    static_wall = time.perf_counter() - t0

    return {
        "points": SWEEP_POINTS,
        "swept_field": "offchip_bw_gbs",
        "sweep_wall_s": sweep_wall,
        "sweep_compiles_per_mechanism": sweep_compiles,
        "static_hw_wall_s": static_wall,
        "static_hw_compiles_per_mechanism": static_compiles,
        "wall_speedup": static_wall / sweep_wall,
    }


def bench_batch_engine(hw: HWParams, cfg: LazyPIMConfig) -> dict:
    """Geometry-bucketed batch engine on the full extended fig7 fleet
    (22 workloads × 6 mechanisms), three walls with *measured* compiles:

    * ``per_workload_jit`` — the pre-batching behavior, reproduced
      faithfully: workload ``name``/``threads`` are static pytree metadata,
      so every workload recompiled every mechanism (fresh jit wrappers +
      named traces — what the committed 162 s fig7 wall was made of);
    * ``sequential`` — post-PR ``run_all``: ``neutral_trace`` keys the jit
      cache on geometry, one compile per (mechanism, geometry);
    * ``batched`` — the ``Study`` planner: one compile per (mechanism,
      bucket), whole fleet vmapped over the stacked workload axis, with
      ``Study.plan()``'s prediction recorded next to the measurement.

    Runs FIRST in the bench (cold jit caches) so the compile counts are the
    fleet's, not leftovers from other sections.  End-to-end walls add the
    shared trace-generation + prepare time to each engine's sim wall.
    """
    pairs = all_workloads(extended=True)
    t0 = time.perf_counter()
    tts = [prepare(make_trace(a, g, threads=16)) for a, g in pairs]
    prep_s = time.perf_counter() - t0

    # --- before: one jit entry per (workload, mechanism), as pre-PR -------
    named_fns = {m: jax.jit(fn) for m, fn in ACC_FNS.items()}
    named_fns["lazypim"] = jax.jit(_lazypim_acc)
    t0 = time.perf_counter()
    for tt in tts:
        for m, fn in named_fns.items():
            args = (tt, hw, cfg) if m == "lazypim" else (tt, hw)
            jax.block_until_ready(fn(*args))
    per_workload_s = time.perf_counter() - t0
    per_workload_compiles = sum(f._cache_size() for f in named_fns.values())

    # --- sequential run_all (geometry-keyed compiles) ---------------------
    seq_before = sequential_cache_sizes()
    t0 = time.perf_counter()
    for tt in tts:
        run_all(tt, hw, lazy_cfg=cfg)
    seq_s = time.perf_counter() - t0
    seq_after = sequential_cache_sizes()
    seq_compiles = sum(seq_after[m] - seq_before[m] for m in seq_after)

    # --- batched Study planner (bucket-keyed compiles) --------------------
    study = Study(workloads=tts, hw=hw, lazy=cfg)
    plan = study.plan()
    bat_before = sweep_cache_sizes()
    t0 = time.perf_counter()
    study.run()
    bat_s = time.perf_counter() - t0
    bat_after = sweep_cache_sizes()
    bat_per_mech = {m: bat_after[m] - bat_before[m] for m in bat_after}
    bat_compiles = sum(bat_per_mech.values())

    return {
        "workloads": len(pairs),
        "mechanisms": 6,
        "trace_gen_prepare_s": prep_s,
        "buckets": [dict(b) for b in plan.buckets],
        "plan_compiles_per_mechanism": plan.compiles_per_mechanism,
        "plan_total_compiles": plan.total_compiles,
        "plan_matches_measured": bat_per_mech == plan.compiles_per_mechanism,
        "per_workload_jit": {"sim_wall_s": per_workload_s,
                             "end_to_end_s": prep_s + per_workload_s,
                             "measured_compiles": per_workload_compiles},
        "sequential": {"sim_wall_s": seq_s,
                       "end_to_end_s": prep_s + seq_s,
                       "measured_compiles": seq_compiles},
        "batched": {"sim_wall_s": bat_s,
                    "end_to_end_s": prep_s + bat_s,
                    "measured_compiles": bat_compiles,
                    "measured_compiles_per_mechanism": bat_per_mech},
        "compile_budget": FLEET_COMPILE_BUDGET,
        "within_budget": bat_compiles <= FLEET_COMPILE_BUDGET,
        "fig7_wall_reduction_vs_per_workload_jit":
            (prep_s + per_workload_s) / (prep_s + bat_s),
        "fig7_wall_reduction_vs_sequential": (prep_s + seq_s) / (prep_s + bat_s),
    }


def bench_trace_synth() -> dict:
    """On-device jit generation vs the sequential numpy reference, per
    family; steady state = min over samples, compile + one warm call
    excluded on the JAX side (the reference has no compile)."""
    out = {}
    for app, g, kw in SYNTH_CASES:
        kw = dict(kw)
        label = kw.pop("label", f"{app}-{g}" if g else app)
        plan, edges, _ = build_plan(app, g, threads=16, seed=0, **kw)
        fn, args = synth.generator(plan, seed=0, edges=edges)

        jax.block_until_ready(fn(*args))          # compile
        jax.block_until_ready(fn(*args))          # warm
        jax_s = float("inf")
        for _ in range(SAMPLES):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            jax_s = min(jax_s, time.perf_counter() - t0)

        ref_s = float("inf")
        for _ in range(max(2, SAMPLES - 2)):
            t0 = time.perf_counter()
            _traceref.synthesize_ref(plan, seed=0, edges=edges)
            ref_s = min(ref_s, time.perf_counter() - t0)

        out[label] = {
            "num_lines": plan.total_lines,
            "num_windows": plan.num_windows,
            "jax_ms": jax_s * 1e3,
            "ref_ms": ref_s * 1e3,
            "jax_windows_per_sec": plan.num_windows / jax_s,
            "ref_windows_per_sec": plan.num_windows / ref_s,
            "speedup": ref_s / jax_s,
        }
    largest = max(out, key=lambda k: out[k]["num_lines"])
    out["largest_workload"] = {"name": largest,
                               "speedup": out[largest]["speedup"]}
    return out


MESH_DEVICE_COUNTS = (1, 2, 4)


def bench_mesh_leg(study: Study, devices: int) -> dict:
    """One point of the mesh-scaling curve: run ``study`` (one bucket) at
    ``devices``, cold then warm, and cross-check ``Study.plan()``'s compile
    prediction against the measured jit-cache deltas (every device count
    compiles its own mesh variant, so the cold run is a cold compile)."""
    plan = study.plan(devices=devices)
    (bucket,) = plan.buckets
    before = sweep_cache_sizes()
    t0 = time.perf_counter()
    study.run(devices=devices)
    cold_s = time.perf_counter() - t0
    after = sweep_cache_sizes()
    measured = {m: after[m] - before[m] for m in after}

    t0 = time.perf_counter()
    study.run(devices=devices)
    warm_s = time.perf_counter() - t0
    return {
        "devices": devices,
        "visible_devices": mesh.available_devices(),
        "lanes": study.num_points,
        "padded_lanes": bucket["padded_lanes"],
        "routed_devices": bucket["devices"],
        "bucket_num_lines": bucket["num_lines"],
        "plan_compiles_per_mechanism": plan.compiles_per_mechanism,
        "measured_compiles_per_mechanism": measured,
        "plan_matches_measured": measured == plan.compiles_per_mechanism,
        "cold_wall_s": cold_s,
        "warm_wall_s": warm_s,
        # One "lane" = one (workload, hw) point simulated through every
        # mechanism; warm wall excludes compiles, so this is the scaling
        # quantity (same lane work at every device count — 8 % d == 0, no
        # padding confound).
        "lanes_per_sec": study.num_points / warm_s,
    }


def bench_mesh_scaling(device_counts=MESH_DEVICE_COUNTS) -> dict:
    """Sharded-dispatch throughput vs device count: lanes/sec of the large
    study (8 lanes in the 1 Mi-line bucket) at every count in
    ``device_counts`` that this process can see, all in this one process
    (a chip belongs to one process).  The device count is fixed at backend
    init, so on CPU force it before launch
    (``XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=4``); ``check_budget.check_mesh``
    gates the committed record on plan == measured at every count."""
    study = large_study()
    study.traces()  # trace synthesis outside every timed region
    visible = mesh.available_devices()
    legs = {str(d): bench_mesh_leg(study, d)
            for d in device_counts if d <= visible}
    base = legs[str(device_counts[0])]["lanes_per_sec"]
    return {
        **legs,
        "scaling_vs_1_device": {d: legs[d]["lanes_per_sec"] / base
                                for d in legs},
        "note": "simulated CPU devices share the host's cores, so the "
                "scaling ceiling is intra-op parallelism already present "
                "at 1 device — the gate checks correctness (plan == "
                "measured per device count) and throughput > 0, not a "
                "linear speedup",
    }


def run() -> dict:
    hw, cfg = HWParams(), LazyPIMConfig()
    return {
        "backend": jax.default_backend(),
        # batch_engine runs FIRST: its compile counts need cold jit caches.
        "batch_engine": bench_batch_engine(hw, cfg),
        "steady_state": bench_mechanisms(hw, cfg),
        "fig7_end_to_end_extended": bench_fig7_wall(hw),
        "hw_sweep": bench_sweep(hw, cfg),
        "trace_synth": bench_trace_synth(),
        # Its own bucket geometry and mesh variants: cold compiles
        # whatever ran before.
        "mesh_scaling": bench_mesh_scaling(),
    }


def main():
    results = run()
    out_path = write_bench_json("engine", results)
    be = results["batch_engine"]
    print(f"batch_engine,buckets,{len(be['buckets'])},compiles,"
          f"{be['batched']['measured_compiles']},budget,{be['compile_budget']},"
          f"e2e_before_s,{be['per_workload_jit']['end_to_end_s']:.1f},"
          f"e2e_seq_s,{be['sequential']['end_to_end_s']:.1f},"
          f"e2e_batched_s,{be['batched']['end_to_end_s']:.1f},"
          f"reduction,{be['fig7_wall_reduction_vs_per_workload_jit']:.2f}x")
    for name, wl in results["steady_state"].items():
        for mech, r in wl["mechanisms"].items():
            print(f"{name},{mech},packed_ms,{r['packed_ms']:.2f},bool_ms,"
                  f"{r['bool_ms']:.2f},speedup,{r['speedup']:.2f}")
    f7 = results["fig7_end_to_end_extended"]
    print(f"fig7_wall_ext,packed_s,{f7['packed_s']:.1f},bool_s,{f7['bool_s']:.1f},"
          f"speedup,{f7['speedup']:.2f}")
    sw = results["hw_sweep"]
    print(f"sweep_{sw['points']}pt,compiles,"
          f"{max(sw['sweep_compiles_per_mechanism'].values())},"
          f"static_compiles,{max(sw['static_hw_compiles_per_mechanism'].values())},"
          f"wall_speedup,{sw['wall_speedup']:.2f}")
    for name, r in results["trace_synth"].items():
        if name == "largest_workload":
            continue
        print(f"synth,{name},lines,{r['num_lines']},jax_ms,{r['jax_ms']:.2f},"
              f"ref_ms,{r['ref_ms']:.2f},speedup,{r['speedup']:.1f}")
    ms = results["mesh_scaling"]
    for d in ms["scaling_vs_1_device"]:
        leg = ms[d]
        print(f"mesh,{d}dev,lanes_per_sec,{leg['lanes_per_sec']:.4f},"
              f"plan_matches,{leg['plan_matches_measured']},"
              f"scaling,{ms['scaling_vs_1_device'][d]:.2f}x")
    print(f"wrote,{out_path}")


if __name__ == "__main__":
    main()
