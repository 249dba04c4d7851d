"""The resident study service, deterministically: admission control,
backpressure, retry/backoff, deadline + hang cancellation, graceful
degradation (bit-exact with the sequential reference), warm-manifest
round-trips, and crash-safe restart with zero new scan compiles."""

import numpy as np
import pytest

from repro.serve import (
    OK,
    OK_DEGRADED,
    REJECTED_MALFORMED,
    REJECTED_OVERLOAD,
    REJECTED_OVERSIZED,
    TIMEOUT,
    BoundedQueue,
    ChaosConfig,
    ChaosMonkey,
    RetryPolicy,
    ServeConfig,
    StudyServer,
    VirtualClock,
    WallClock,
    build_study,
    restart_server,
)
from repro.sim import engine as _engine

SMALL = dict(num_kernels=3, windows_per_kernel=2)
SPEC = {
    "workloads": [{"app": "pagerank", "graph": "arxiv", "scale": 0.4,
                   **SMALL}],
    "mechanisms": ["cpu", "lazypim"],
    "threads": 16,
}


def _server(clock=None, chaos=None, **cfg_kw):
    cfg_kw.setdefault("default_deadline_s", 1e9)
    return StudyServer(ServeConfig(**cfg_kw), clock=clock or VirtualClock(),
                       chaos=chaos)


def _assert_rows_equal(a, b):
    ra, rb = a.to_rows(), b.to_rows()
    assert len(ra) == len(rb)
    for x, y in zip(ra, rb):
        assert x.keys() == y.keys()
        for k in x:
            if isinstance(x[k], float):
                np.testing.assert_array_equal(x[k], y[k]), k
            else:
                assert x[k] == y[k], k


# -- clocks and queue --------------------------------------------------------


def test_virtual_clock_sleep_advances():
    c = VirtualClock()
    t0 = c.now()
    c.sleep(2.5)
    c.advance(1.0)
    assert c.now() == t0 + 3.5
    assert c.slept == 2.5  # advance() is ambient time, not a sleep


def test_wall_clock_is_monotonic():
    c = WallClock()
    assert c.now() <= c.now()


def test_bounded_queue_sheds_when_full():
    q = BoundedQueue(2)
    assert q.offer("a") and q.offer("b")
    assert not q.offer("c")
    assert q.shed == 1 and q.accepted == 2 and len(q) == 2
    assert q.pop() == "a"
    assert q.offer("c")  # capacity freed
    assert q.pop() == "b" and q.pop() == "c" and q.pop() is None


# -- retry policy ------------------------------------------------------------


def test_backoff_deterministic_and_bounded():
    p1 = RetryPolicy(max_attempts=5, base_s=0.1, cap_s=1.0, seed=7)
    p2 = RetryPolicy(max_attempts=5, base_s=0.1, cap_s=1.0, seed=7)
    for rid in range(5):
        for attempt in range(1, 5):
            b = p1.backoff_s(rid, attempt)
            assert b == p2.backoff_s(rid, attempt)  # replayable
            raw = min(1.0, 0.1 * 2 ** (attempt - 1))
            assert raw / 2 <= b < raw  # jitter keeps [raw/2, raw)
    # Different seeds / rids de-synchronize.
    p3 = RetryPolicy(max_attempts=5, base_s=0.1, cap_s=1.0, seed=8)
    assert p3.backoff_s(0, 1) != p1.backoff_s(0, 1)
    assert p1.backoff_s(0, 1) != p1.backoff_s(1, 1)


def test_retry_policy_rejects_zero_attempts():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)


# -- admission ---------------------------------------------------------------


def test_service_ema_zero_observation_decays_instead_of_reseeding():
    # Regression: the estimator's "unset" sentinel used to be == 0.0, so a
    # legitimate zero-duration observation (exactly what a virtual clock
    # produces for an instant dispatch) put the EMA back into the "never
    # observed" state and the NEXT sample hard-reset it instead of
    # decaying — one slow step after a fast one re-seeded the estimate to
    # the full slow value.  Unset is now None; 0.0 is data.
    srv = StudyServer(ServeConfig(), clock=VirtualClock())
    assert srv._service_ema is None       # never observed
    srv._observe_service(10.0)
    assert srv._service_ema == 10.0       # first sample seeds
    srv._observe_service(0.0)
    assert srv._service_ema == pytest.approx(8.0)   # 0.8*10 + 0.2*0
    srv2 = StudyServer(ServeConfig(), clock=VirtualClock())
    srv2._observe_service(0.0)
    assert srv2._service_ema == 0.0       # a real observation, not "unset"
    srv2._observe_service(10.0)
    assert srv2._service_ema == pytest.approx(2.0)  # decays, no hard reset


def test_malformed_spec_rejected_with_naming_error():
    srv = _server()
    resp = srv.submit({"workloads": ["not-a-real-app"]})
    assert resp.status == REJECTED_MALFORMED
    assert "not-a-real-app" in resp.error


def test_a_generated_graph_is_served_and_a_built_in_graph_refuses_its_keys():
    kron = {"app": "bfs", "graph": "kronecker", "kron_scale": 10,
            "edge_factor": 16, "graph_seed": 1, **SMALL}
    srv = _server()
    rid = srv.submit({"workloads": [kron], "mechanisms": ["cpu", "lazypim"]})
    assert isinstance(rid, int)
    (resp,) = srv.drain()
    assert resp.status == OK and resp.rid == rid
    (point,) = resp.results.points
    assert point.workload == "bfs-kronecker"
    assert set(point.results) == {"cpu", "lazypim"}
    bad = dict(SPEC, workloads=[{**SPEC["workloads"][0], "graph_seed": 1}])
    resp = srv.submit(bad)
    assert resp.status == REJECTED_MALFORMED
    assert "workloads[0]" in resp.error and "graph_seed" in resp.error


def test_oversized_request_rejected_by_lane_bound():
    srv = _server(max_lanes=4)
    big = dict(SPEC, hw_grid={"offchip_bw_gbs": [float(b) for b in
                                                 range(16, 26)]})
    resp = srv.submit(big)
    assert resp.status == REJECTED_OVERSIZED
    assert "10 lanes" in resp.error


def test_overload_sheds_and_rids_stay_sequential():
    srv = _server(max_queue=2)
    outcomes = [srv.submit(SPEC) for _ in range(4)]
    assert outcomes[0] == 0 and outcomes[1] == 1  # queued: rid returned
    assert outcomes[2].status == REJECTED_OVERLOAD
    assert outcomes[2].rid == 2  # rejected submissions consume rids too
    assert outcomes[3].rid == 3
    assert srv.queue.shed == 2


# -- serving, retries, degradation ------------------------------------------


def test_clean_request_served_by_batched_planner():
    srv = _server()
    rid = srv.submit(SPEC)
    resp = srv.drain()[0]
    assert resp.rid == rid and resp.status == OK
    assert resp.engine == "batch" and resp.attempts == 1
    _assert_rows_equal(resp.results, build_study(SPEC).run("sequential"))


def test_transient_failure_retries_to_success_with_backoff():
    clock = VirtualClock()
    monkey = ChaosMonkey(ChaosConfig(seed=0, fault_rate=1.0,
                                     classes=("engine_exception",),
                                     transient_fraction=1.0), clock=clock)
    srv = _server(clock=clock, chaos=monkey, backoff_base_s=0.25)
    srv.submit(SPEC)
    resp = srv.drain()[0]
    assert resp.status == OK and resp.attempts == 2
    assert srv.stats["retry_successes"] == 1
    assert clock.slept > 0  # the backoff actually waited
    assert resp.latency_s >= clock.slept


def test_persistent_failure_degrades_bit_exact():
    monkey = ChaosMonkey(ChaosConfig(seed=0, fault_rate=1.0,
                                     classes=("engine_exception",),
                                     transient_fraction=0.0))
    srv = _server(chaos=monkey, max_attempts=2)
    srv.submit(SPEC)
    resp = srv.drain()[0]
    assert resp.status == OK_DEGRADED and resp.engine == "sequential"
    assert resp.attempts == 2 and "degraded" in resp.error
    # A degraded answer is never a wrong answer: bit-exact with the
    # fault-free sequential reference.
    _assert_rows_equal(resp.results, build_study(SPEC).run("sequential"))


def test_deadline_exceeded_before_dispatch_times_out():
    clock = VirtualClock()
    srv = _server(clock=clock, default_deadline_s=5.0)
    srv.submit(SPEC)
    clock.advance(6.0)  # request goes stale while queued
    resp = srv.drain()[0]
    assert resp.status == TIMEOUT and "deadline" in resp.error


def test_hang_detected_by_heartbeat_and_worker_cordoned():
    clock = VirtualClock()
    monkey = ChaosMonkey(ChaosConfig(seed=0, fault_rate=1.0,
                                     classes=("hang",), hang_s=60.0),
                         clock=clock)
    srv = _server(clock=clock, chaos=monkey, default_deadline_s=30.0,
                  heartbeat_timeout_s=20.0)
    srv.submit(SPEC)
    resp = srv.drain()[0]
    assert resp.status == TIMEOUT and "hang" in resp.error
    assert srv.stats["hangs_detected"] == 1
    # remove_host ran: the hung worker no longer poisons later requests...
    assert srv.hb.dead_hosts(now=clock.now()) == []
    assert [p["action"] for p in srv.restart_plans] == ["remesh"]
    # ...so the very next request on the replacement worker serves fine.
    monkey.exempt.add(1)
    srv.submit(SPEC)
    assert srv.drain()[0].status == OK


# -- warm manifest + crash-safe restart --------------------------------------


def test_warm_manifest_roundtrip_idempotent(tmp_path):
    srv = _server(cache_dir=str(tmp_path))
    srv.submit(SPEC)
    assert srv.drain()[0].status == OK
    entries = srv.warm.load_manifest()
    assert len(entries) == 2  # one per mechanism, single geometry bucket
    assert {e["mechanism"] for e in entries} == {"cpu", "lazypim"}
    assert all(e["lanes"] == 1 for e in entries)
    # Re-serving the same study adds nothing (idempotent merge).
    srv.submit(SPEC)
    srv.drain()
    assert srv.warm.load_manifest() == entries


def test_crash_keeps_journal_and_restart_replays(tmp_path):
    cfg = dict(cache_dir=str(tmp_path), default_deadline_s=1e9)
    monkey = ChaosMonkey(ChaosConfig(seed=0, fault_rate=1.0,
                                     classes=("crash",)))
    srv = _server(chaos=monkey, **cfg)
    rid = srv.submit(SPEC)
    srv.submit(SPEC)  # still queued when the worker dies
    resp = srv.step()
    assert resp.status == "crashed" and srv.crashed
    assert srv.step() is None  # a crashed server serves nothing
    assert sorted(srv._journal) == [0, 1]  # both unresolved rids journaled

    srv2, replayed = restart_server(
        ServeConfig(**cfg),
        chaos=ChaosMonkey(ChaosConfig(seed=0, fault_rate=1.0,
                                      classes=("crash",))))
    assert [(r.rid, r.status, r.restarted) for r in replayed] == \
        [(0, OK, True), (1, OK, True)]
    _assert_rows_equal(replayed[0].results,
                       build_study(SPEC).run("sequential"))
    assert srv2._journal == {}  # replay resolved and cleared the journal
    # New submissions never collide with journaled rids.
    assert srv2.submit(SPEC) == 2


def test_restart_answers_from_warm_cache_with_zero_new_compiles(tmp_path):
    cfg = ServeConfig(cache_dir=str(tmp_path), default_deadline_s=1e9)
    srv = StudyServer(cfg, clock=VirtualClock())
    srv.submit(SPEC)
    assert srv.drain()[0].status == OK

    # Simulate process death: the in-process jit caches vanish; the
    # persistent compile cache and the warm manifest survive on disk.
    _engine._sweep_fn.cache_clear()
    srv2, replayed = restart_server(cfg, clock=VirtualClock())
    assert replayed == []  # nothing was in flight
    assert srv2.stats["warmed_entries"] == 2

    before = dict(_engine.sweep_cache_sizes())
    srv2.submit(SPEC)
    resp = srv2.drain()[0]
    after = dict(_engine.sweep_cache_sizes())
    assert resp.status == OK and resp.engine == "batch"
    assert after == before  # zero new scan compiles for a repeat study
    _assert_rows_equal(resp.results, build_study(SPEC).run("sequential"))
