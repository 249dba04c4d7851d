"""Serve-layer benchmark: request storm under fault injection + crash-safe
warm-restart of the fig7 fleet.

    PYTHONPATH=src python -m benchmarks.run --bench serve

Two legs, one ``BENCH_serve.json`` record:

* **storm** — a resident :class:`repro.serve.StudyServer` on the wall
  clock answers a synthetic storm of small study requests with 10%
  injected faults (all five chaos classes).  Reports p50/p99 served-study
  latency, steady-state studies/sec, and the outcome histogram — the
  service-level claim that fault handling costs the fault, not the fleet.
* **warm_restart** — serve THE fig7 study cold (the full 18-compile
  fleet), simulate a worker crash (in-process jit caches wiped), restart
  from the persistent compile cache + warm manifest, and re-answer fig7.
  Records cold/warm timings and the measured post-restart scan-compile
  count, which must be **zero** (gated by ``benchmarks.check_budget``
  against the committed record, like the fleet compile budget).
* **coalesce** — a queue of 16 interactive-sized repeat studies, served
  one-at-a-time (the PR-6 loop) vs coalesced into blessed-width shared
  dispatches (``ServeConfig(coalesce=True)``).  Both legs measure the
  *warm* steady state (compile keys hot, resident studies cached);
  reports studies/sec for each, the speedup (gated >= 2x), the one-time
  blessed-width compile count, and the steady-state scan-compile delta,
  which must be **zero** — blessed widths are the proof coalescing
  cannot explode the compile-key space.
* **policy** — the adaptive coalescing policy (``ServeConfig(
  adaptive=True)``) vs the greedy coalescer: depth-1 p50 latency must not
  regress (no backlog -> no formation hold), depth-16 throughput must
  keep the >= 2x gate (deep queues form immediately), and the
  steady-state scan-compile delta with the policy on must be **zero**.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.timing import write_bench_json
from repro.serve import (
    ChaosConfig,
    ChaosMonkey,
    ServeConfig,
    StudyServer,
    fresh_serve_dir,
    make_storm,
    restart_server,
)
from repro.sim import engine as _engine

STORM_N = 60
STORM_SEED = 0
FAULT_RATE = 0.10

_SMALL = dict(num_kernels=3, windows_per_kernel=2)
BASE_SPECS = [
    {"workloads": [{"app": "pagerank", "graph": "arxiv", "scale": 0.4,
                    **_SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
    {"workloads": [{"app": "htap128", "scale": 0.004, **_SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
]


def bench_storm() -> dict:
    cache_dir = str(fresh_serve_dir("bench-storm"))
    # Real-time chaos: the hang must outlive the heartbeat timeout for
    # detection; the timeout must in turn outlive legitimate inter-beat
    # gaps (trace synthesis before the first dispatch of a request).
    monkey = ChaosMonkey(
        ChaosConfig(seed=STORM_SEED, fault_rate=FAULT_RATE, hang_s=12.0))
    cfg = ServeConfig(default_deadline_s=300.0, heartbeat_timeout_s=10.0,
                      backoff_base_s=0.01, backoff_cap_s=0.1,
                      max_queue=STORM_N, max_lanes=64, cache_dir=cache_dir)
    srv = StudyServer(cfg, chaos=monkey)
    monkey.clock = srv.clock

    # Pre-warm the two base geometries outside the measured storm (compile
    # time is the engine benchmark's subject, not the serve loop's).
    for rid, spec in enumerate(BASE_SPECS):
        monkey.exempt.add(rid)
        srv.submit(spec)
    assert all(r.served for r in srv.drain())

    storm = make_storm(monkey, STORM_N, BASE_SPECS,
                       first_rid=srv._next_rid)
    t0 = time.perf_counter()
    final = {}
    for spec in storm:
        out = srv.submit(spec)
        if not isinstance(out, int):
            final[out.rid] = out
    for r in srv.drain():
        final[r.rid] = r
    restarts = 0
    while srv.crashed:
        restarts += 1
        srv, replayed = restart_server(cfg, chaos=monkey)
        for r in [*replayed, *srv.drain()]:
            final[r.rid] = r
    wall_s = time.perf_counter() - t0

    served = [r for r in final.values() if r.served]
    lat = np.array([r.latency_s for r in served])
    outcomes = {}
    for r in final.values():
        outcomes[r.status] = outcomes.get(r.status, 0) + 1
    injected = {}
    for _, kind in monkey.injected:
        injected[kind] = injected.get(kind, 0) + 1
    return {
        "n_requests": STORM_N,
        "seed": STORM_SEED,
        "fault_rate": FAULT_RATE,
        "outcomes": outcomes,
        "injected": injected,
        "worker_restarts": restarts,
        "served": len(served),
        "p50_latency_s": round(float(np.percentile(lat, 50)), 6),
        "p99_latency_s": round(float(np.percentile(lat, 99)), 6),
        "studies_per_s": round(len(served) / wall_s, 3),
        "storm_wall_s": round(wall_s, 3),
    }


COALESCE_N = 16  # queue depth per measured pass (8 per geometry group)
REPEATS = 3      # steady-state passes; best-of wins (single-core jitter)

# Interactive-sized requests: the load shape coalescing targets — many
# small repeat studies queued behind one resident worker, where the
# per-dispatch overhead (not the scan) dominates the one-at-a-time loop.
COALESCE_SPECS = [
    {"workloads": [{"app": "pagerank", "graph": "arxiv", "scale": 0.01,
                    **_SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
    {"workloads": [{"app": "htap128", "scale": 0.0001, **_SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
]


def bench_coalesce() -> dict:
    specs = [COALESCE_SPECS[i % len(COALESCE_SPECS)]
             for i in range(COALESCE_N)]

    def run_pass(srv):
        rids = [srv.submit(s) for s in specs]
        assert all(isinstance(r, int) for r in rids), "admission rejected"
        t0 = time.perf_counter()
        out = srv.drain()
        wall = time.perf_counter() - t0
        assert len(out) == COALESCE_N
        assert all(r.status == "ok" for r in out), \
            {r.rid: r.status for r in out if r.status != "ok"}
        return wall

    solo = StudyServer(ServeConfig(default_deadline_s=3600.0,
                                   max_queue=COALESCE_N))
    run_pass(solo)  # warm the 1-lane compile keys + resident studies
    solo_s = min(run_pass(solo) for _ in range(REPEATS))

    co = StudyServer(ServeConfig(default_deadline_s=3600.0,
                                 max_queue=COALESCE_N, coalesce=True,
                                 audit_fraction=0.0))
    base = dict(_engine.sweep_cache_sizes())
    run_pass(co)  # warm the blessed-width compile keys (one-time cost)
    warmed = dict(_engine.sweep_cache_sizes())
    blessed_compiles = sum(warmed.values()) - sum(base.values())
    co_s = min(run_pass(co) for _ in range(REPEATS))
    after = dict(_engine.sweep_cache_sizes())
    new_compiles = sum(after.values()) - sum(warmed.values())
    assert new_compiles == 0, \
        f"steady-state coalescing recompiled {new_compiles} scans"
    groups_per_pass = int(co.stats["coalesced_groups"]) // (1 + REPEATS)
    return {
        "queue_depth": COALESCE_N,
        "one_at_a_time_studies_per_s": round(COALESCE_N / solo_s, 3),
        "coalesced_studies_per_s": round(COALESCE_N / co_s, 3),
        "speedup": round(solo_s / co_s, 3),
        "coalesced_dispatch_groups": groups_per_pass,
        "blessed_width_compiles": int(blessed_compiles),
        "new_scan_compiles_at_steady_state": int(new_compiles),
    }


def bench_policy() -> dict:
    """The adaptive-policy claim, measured: at depth 1 the policy adds no
    latency over the greedy coalescer (no backlog -> no hold, same
    dispatch); at depth 16 it keeps the greedy deep-queue path and its
    >= 2x throughput gate (zero formation holds); and it mints zero new
    scan compile keys at steady state — blessed widths stay the only jit
    key space with the policy on."""
    spec = COALESCE_SPECS[0]

    def serve_one(srv):
        rid = srv.submit(spec)
        assert isinstance(rid, int), "admission rejected"
        t0 = time.perf_counter()
        (r,) = srv.drain()
        assert r.status == "ok", r.status
        return time.perf_counter() - t0

    greedy = StudyServer(ServeConfig(default_deadline_s=3600.0,
                                     max_queue=COALESCE_N, coalesce=True,
                                     audit_fraction=0.0))
    adaptive = StudyServer(ServeConfig(default_deadline_s=3600.0,
                                       max_queue=COALESCE_N, coalesce=True,
                                       adaptive=True, audit_fraction=0.0))
    for srv in (greedy, adaptive):  # warm compile keys + resident studies
        for _ in range(5):
            serve_one(srv)

    # Depth-1 latency, fairly interleaved: alternate the servers within
    # each round so clock drift hits both; min-of-round-medians beats the
    # single-core jitter.
    g_p50s, a_p50s = [], []
    for _ in range(REPEATS):
        g_lat, a_lat = [], []
        for _ in range(20):
            g_lat.append(serve_one(greedy))
            a_lat.append(serve_one(adaptive))
        g_p50s.append(float(np.median(g_lat)))
        a_p50s.append(float(np.median(a_lat)))

    # Depth-16 throughput: deep queues must form immediately (the PR-7
    # path), so the adaptive leg re-earns the >= 2x coalescing gate.
    specs = [COALESCE_SPECS[i % len(COALESCE_SPECS)]
             for i in range(COALESCE_N)]

    def run_pass(srv):
        rids = [srv.submit(s) for s in specs]
        assert all(isinstance(r, int) for r in rids), "admission rejected"
        t0 = time.perf_counter()
        out = srv.drain()
        wall = time.perf_counter() - t0
        assert len(out) == COALESCE_N
        assert all(r.status == "ok" for r in out), \
            {r.rid: r.status for r in out if r.status != "ok"}
        return wall

    solo = StudyServer(ServeConfig(default_deadline_s=3600.0,
                                   max_queue=COALESCE_N))
    run_pass(solo)  # warm the 1-lane compile keys + resident studies
    solo_s = min(run_pass(solo) for _ in range(REPEATS))

    run_pass(adaptive)  # warm the wide blessed widths (one-time cost)
    base = dict(_engine.sweep_cache_sizes())
    holds0 = int(adaptive.stats["formation_holds"])
    adapt_s = min(run_pass(adaptive) for _ in range(REPEATS))
    after = dict(_engine.sweep_cache_sizes())
    new_compiles = sum(after.values()) - sum(base.values())
    assert new_compiles == 0, \
        f"adaptive steady state recompiled {new_compiles} scans"
    holds = int(adaptive.stats["formation_holds"]) - holds0
    assert holds == 0, f"depth-16 passes held for formation {holds}x"
    return {
        "depth1_p50_greedy_s": round(min(g_p50s), 6),
        "depth1_p50_adaptive_s": round(min(a_p50s), 6),
        "depth16_one_at_a_time_studies_per_s":
            round(COALESCE_N / solo_s, 3),
        "depth16_adaptive_studies_per_s": round(COALESCE_N / adapt_s, 3),
        "adaptive_speedup": round(solo_s / adapt_s, 3),
        "formation_holds_at_depth16": holds,
        "new_scan_compiles_at_steady_state": int(new_compiles),
        "telemetry": adaptive.telemetry.summary(),
    }


def bench_warm_restart() -> dict:
    from benchmarks.fig7_speedup import study as fig7_study

    cache_dir = str(fresh_serve_dir("bench-warm"))
    cfg = ServeConfig(default_deadline_s=3600.0, cache_dir=cache_dir)

    srv = StudyServer(cfg)
    t0 = time.perf_counter()
    srv.submit(fig7_study())
    assert srv.drain()[0].status == "ok"
    cold_s = time.perf_counter() - t0
    manifest = srv.warm.load_manifest()

    # Crash: the process's jit caches die; disk cache + manifest survive.
    _engine._sweep_fn.cache_clear()
    t0 = time.perf_counter()
    srv2, _ = restart_server(cfg)
    warm_boot_s = time.perf_counter() - t0

    before = dict(_engine.sweep_cache_sizes())
    t0 = time.perf_counter()
    srv2.submit(fig7_study())
    assert srv2.drain()[0].status == "ok"
    warm_serve_s = time.perf_counter() - t0
    after = dict(_engine.sweep_cache_sizes())
    new_compiles = sum(after.values()) - sum(before.values())
    assert new_compiles == 0, \
        f"warm restart recompiled {new_compiles} scans"
    return {
        "manifest_entries": len(manifest),
        "cold_serve_s": round(cold_s, 2),
        "warm_boot_s": round(warm_boot_s, 2),
        "warm_serve_s": round(warm_serve_s, 2),
        "new_scan_compiles_after_restart": new_compiles,
    }


def main() -> None:
    storm = bench_storm()
    print(f"storm: {storm['served']}/{storm['n_requests']} served, "
          f"p50 {storm['p50_latency_s'] * 1e3:.1f} ms, "
          f"p99 {storm['p99_latency_s'] * 1e3:.1f} ms, "
          f"{storm['studies_per_s']:.1f} studies/s, "
          f"outcomes {storm['outcomes']}")
    warm = bench_warm_restart()
    print(f"warm restart: {warm['manifest_entries']} manifest entries, "
          f"cold {warm['cold_serve_s']}s -> boot {warm['warm_boot_s']}s + "
          f"serve {warm['warm_serve_s']}s, "
          f"{warm['new_scan_compiles_after_restart']} new scan compiles")
    coalesce = bench_coalesce()
    print(f"coalesce: depth {coalesce['queue_depth']}, "
          f"{coalesce['one_at_a_time_studies_per_s']:.1f} -> "
          f"{coalesce['coalesced_studies_per_s']:.1f} studies/s "
          f"({coalesce['speedup']:.2f}x), "
          f"{coalesce['blessed_width_compiles']} blessed-width compiles, "
          f"{coalesce['new_scan_compiles_at_steady_state']} at steady state")
    policy = bench_policy()
    print(f"policy: depth-1 p50 greedy "
          f"{policy['depth1_p50_greedy_s'] * 1e3:.1f} ms vs adaptive "
          f"{policy['depth1_p50_adaptive_s'] * 1e3:.1f} ms, depth-16 "
          f"{policy['depth16_adaptive_studies_per_s']:.1f} studies/s "
          f"({policy['adaptive_speedup']:.2f}x), "
          f"{policy['formation_holds_at_depth16']} deep-queue holds, "
          f"{policy['new_scan_compiles_at_steady_state']} new compiles")
    path = write_bench_json("serve", {"storm": storm, "warm_restart": warm,
                                      "coalesce": coalesce,
                                      "policy": policy})
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
