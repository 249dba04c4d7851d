"""Fault-isolated cross-request lane coalescing: bit-exactness vs the
one-at-a-time loop, masked pad lanes, bisection isolation of poison
requests (chaos storms over >= 3 seeds), per-lane integrity sentinels,
audit-mismatch degradation, blessed-width warm/compile-key reuse, and the
PR-7 hardening satellites (deadline-at-admission shed, manifest/journal
quarantine-and-rebuild, ResultSet schema errors).

Set ``REPRO_CHAOS_SEED`` to pin a single seed (the CI fault-injection
legs run one seed per matrix entry).
"""

import json
import os

import jax
import numpy as np
import pytest

from repro.serve import (
    BLESSED_LANE_WIDTHS,
    OK,
    OK_DEGRADED,
    QUARANTINED,
    SERVED,
    BoundedQueue,
    ChaosConfig,
    ChaosMonkey,
    InjectedEngineError,
    ServeConfig,
    StudyServer,
    VirtualClock,
    audit_sample,
    blessed_width,
    build_study,
    group_key,
    restart_server,
    stack_group,
)
from repro.sim import engine as _engine
from repro.sim.study import ResultSet, ResultSetSchemaError

SEEDS = ([int(os.environ["REPRO_CHAOS_SEED"])]
         if "REPRO_CHAOS_SEED" in os.environ else [0, 1, 2])

SMALL = dict(num_kernels=3, windows_per_kernel=2)
SPEC_A = {
    "workloads": [{"app": "pagerank", "graph": "arxiv", "scale": 0.4,
                   **SMALL}],
    "mechanisms": ["cpu", "lazypim"],
    "threads": 16,
}
SPEC_B = {
    "workloads": [{"app": "htap128", "scale": 0.004, **SMALL}],
    "mechanisms": ["cpu", "lazypim"],
    "threads": 16,
}
# Same geometry as SPEC_A but a 2-point hw axis: coalesces with it.
SPEC_A2 = {**SPEC_A, "hw_grid": {"offchip_bw_gbs": [16.0, 32.0]}}


def _server(clock=None, chaos=None, **cfg_kw):
    cfg_kw.setdefault("default_deadline_s", 1e9)
    cfg_kw.setdefault("coalesce", True)
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


# -- pure mechanics ----------------------------------------------------------


def test_blessed_width_rounds_up_to_pow2():
    assert [blessed_width(n) for n in (1, 2, 3, 4, 5, 8, 9, 64)] == \
        [1, 2, 4, 4, 8, 8, 16, 64]
    with pytest.raises(ValueError):
        blessed_width(0)
    with pytest.raises(ValueError):
        blessed_width(BLESSED_LANE_WIDTHS[-1] + 1)


def test_audit_sample_is_deterministic_and_bounded():
    s1 = audit_sample(0, 7, 16, 0.25)
    s2 = audit_sample(0, 7, 16, 0.25)
    assert s1 == s2 and len(s1) == 4
    assert all(0 <= i < 16 for i in s1) and sorted(set(s1)) == s1
    assert audit_sample(0, 8, 16, 0.25) != s1  # per-dispatch stream
    assert audit_sample(0, 7, 16, 0.0) == []
    assert audit_sample(0, 7, 5, 1.0) == [0, 1, 2, 3, 4]
    assert len(audit_sample(0, 7, 16, 0.01)) == 1  # at least one lane


def test_queue_take_removes_matches_preserving_order():
    q = BoundedQueue(8)
    for x in (1, 2, 3, 4, 5):
        q.offer(x)
    assert q.take(lambda x: x % 2 == 0) == [2, 4]
    assert [q.pop(), q.pop(), q.pop()] == [1, 3, 5]
    assert q.pop() is None


def test_group_key_compatibility():
    ka = group_key(build_study(SPEC_A))
    ka2 = group_key(build_study(SPEC_A2))
    kb = group_key(build_study(SPEC_B))
    assert ka is not None and ka == ka2  # hw axis is per-lane data
    assert ka != kb                      # different geometry bucket
    multi = build_study({**SPEC_A, "workloads": [
        SPEC_A["workloads"][0],
        {"app": "pagerank", "graph": "arxiv", "scale": 0.4,
         "num_kernels": 3, "windows_per_kernel": 40}]})
    if len(multi.bucket_lanes()) > 1:  # windows differ but bucket may merge
        assert group_key(multi) is None


# -- bit-exactness and pad-lane masking --------------------------------------


def test_coalesced_bit_exact_vs_one_at_a_time():
    specs = [SPEC_A, SPEC_B, SPEC_A2, SPEC_A, SPEC_B, SPEC_A, SPEC_A2,
             SPEC_B]  # queue depth 8, three group keys
    co = _server(audit_fraction=1.0)
    for s in specs:
        co.submit(s)
    coalesced = co.drain()

    solo = StudyServer(ServeConfig(default_deadline_s=1e9),
                       clock=VirtualClock())
    for s in specs:
        solo.submit(s)
    baseline = solo.drain()

    assert len(coalesced) == len(baseline) == len(specs)
    assert co.stats["coalesced_dispatches"] >= 1
    # Coalesced drain resolves in group order (the head pulls compatible
    # peers forward), so align by rid — every request must still resolve.
    by_rid = {r.rid: r for r in coalesced}
    assert sorted(by_rid) == sorted(b.rid for b in baseline)
    for b in baseline:
        a = by_rid[b.rid]
        assert a.status == OK and a.engine == "coalesced"
        assert b.status == OK and b.engine == "batch"
        _assert_rows_equal(a.results, b.results)


def test_masked_pad_lanes_never_contribute():
    # Three lanes pad to blessed width 4: one all-sentinel masked lane
    # rides the dispatch.  Every served number must equal the unpadded
    # study run AND the sequential reference, field-exact.
    srv = _server(audit_fraction=0.0)
    for _ in range(3):
        srv.submit(SPEC_A)
    out = srv.drain()
    assert [r.status for r in out] == [OK] * 3
    assert srv.stats["coalesced_dispatches"] == 1
    ref = build_study(SPEC_A).run("sequential")
    for r in out:
        _assert_rows_equal(r.results, ref)


def test_planner_and_coalescer_stack_the_same_pytrees():
    # One stacking seam: for the same three lanes at width 4 (the blessed
    # width, and the 2-device mesh multiple), the planner's bucket stack
    # and the coalescer's group stack are bit-identical, pad lane included.
    st = build_study({**SPEC_A, "hw_grid": {"offchip_bw_gbs": [16.0, 32.0,
                                                              64.0]}})
    (bl,) = st.bucket_lanes()
    planner = st._stack_lanes(bl, 3, 2)
    stt, shw, scfg, _, width = stack_group(group_key(st), [(0, st)])
    assert width == 4
    coalescer = (stt, shw, scfg)
    assert jax.tree.structure(planner) == jax.tree.structure(coalescer)
    for a, b in zip(jax.tree.leaves(planner), jax.tree.leaves(coalescer)):
        assert a.dtype == b.dtype and a.shape[0] == width
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    valid = np.asarray(stt.window_valid)
    assert valid[:3].any(axis=1).all() and not valid[3].any()


def test_multi_bucket_request_falls_back_to_single_request_path():
    spec = {**SPEC_A, "workloads": [
        {"app": "pagerank", "graph": "arxiv", "scale": 0.4, **SMALL},
        {"app": "pagerank", "graph": "arxiv", "scale": 3.0,
         "num_kernels": 3, "windows_per_kernel": 2}]}
    study = build_study(spec)
    if group_key(study) is not None:
        pytest.skip("scales landed in one geometry bucket")
    srv = _server()
    srv.submit(spec)
    (resp,) = srv.drain()
    assert resp.status == OK and resp.engine == "batch"
    _assert_rows_equal(resp.results, build_study(spec).run("sequential"))


# -- poison isolation (the robustness headline) ------------------------------


def _poison_storm(seed, classes, n=8, fault_rate=0.25, audit=1.0):
    clock = VirtualClock()
    monkey = ChaosMonkey(ChaosConfig(seed=seed, fault_rate=fault_rate,
                                     classes=classes), clock=clock)
    srv = _server(clock=clock, chaos=monkey, audit_fraction=audit,
                  seed=seed)
    for _ in range(n):
        srv.submit(SPEC_A)
    out = srv.drain()
    faults = {rid: monkey.fault_for(rid) for rid in range(n)}
    return srv, out, faults


@pytest.mark.parametrize("seed", SEEDS)
def test_poison_lane_bisection_isolates_exactly_the_poison(seed):
    srv, out, faults = _poison_storm(seed, ("poison_lane",))
    poisoned = {rid for rid, f in faults.items() if f == "poison_lane"}
    assert poisoned, f"seed {seed} drew no poison_lane faults; pick another"
    ref = build_study(SPEC_A).run("sequential")
    for r in out:
        if r.rid in poisoned:
            # The offender is quarantined with its bisection trace...
            assert r.status == QUARANTINED
            assert "bisection" in r.error
            rec = srv.quarantine[r.rid]
            assert rec["spec"] == SPEC_A
            assert any("failed" in ev["outcome"]
                       for ev in rec["bisection"])
            # ...and every failed sub-dispatch in its trace contained it.
            for ev in rec["bisection"]:
                if "failed" in ev["outcome"]:
                    assert set(ev["members"]) & poisoned
        else:
            # Healthy co-batched neighbors are never timed out, degraded
            # away, or corrupted: served ok, bit-exact.
            assert r.status == OK, (r.rid, r.status, r.error)
            _assert_rows_equal(r.results, ref)
    assert set(srv.quarantine) == poisoned


@pytest.mark.parametrize("seed", SEEDS)
def test_poison_result_storm_never_serves_a_wrong_answer(seed):
    srv, out, faults = _poison_storm(seed, ("poison_result",),
                                     fault_rate=0.3)
    poisoned = {rid for rid, f in faults.items() if f == "poison_result"}
    assert poisoned, f"seed {seed} drew no poison_result faults"
    injected = dict(srv.chaos.injected)
    ref = build_study(SPEC_A).run("sequential")
    for r in out:
        kind = injected.get(r.rid)
        if kind == "poison_result:nan":
            # NaN trips the finalize sentinel: lane-exact attribution,
            # no bisection needed, straight to quarantine.
            assert r.status == QUARANTINED
            assert "integrity sentinel" in r.error
            assert r.rid in srv.quarantine
        else:
            # Finite corruption anywhere in the batch is caught by the
            # audit, which degrades the whole sub-batch to the sequential
            # reference — so even the poisoned request's answer is
            # *correct* (recomputed), and healthy members always are.
            assert r.status in SERVED, (r.rid, r.status, r.error)
            _assert_rows_equal(r.results, ref)
    if any(k == "poison_result:finite" for k in injected.values()):
        assert srv.stats["audit_mismatches"] >= 1
        assert any(r.status == OK_DEGRADED for r in out)


def test_poison_result_nan_is_lane_attributed():
    # Seed 2 deterministically draws the NaN variant for rid 2 (and only
    # rid 2) at fault_rate 0.3 — neighbors stay ok on the same dispatch.
    srv, out, faults = _poison_storm(2, ("poison_result",), n=6,
                                     fault_rate=0.3)
    statuses = {r.rid: r.status for r in out}
    assert statuses[2] == QUARANTINED
    assert all(s == OK for rid, s in statuses.items() if rid != 2)
    assert list(srv.quarantine) == [2]


class _SlowFaultMonkey(ChaosMonkey):
    """poison_lane faults that burn virtual wall before dying — the cost a
    real clock sees when a poisoned engine execution fails partway in,
    multiplied across every bisection sub-dispatch containing the poison."""

    def __init__(self, cfg, clock, fault_wall_s):
        super().__init__(cfg, clock=clock)
        self.fault_wall_s = fault_wall_s

    def on_coalesced_dispatch(self, rids, dispatch):
        try:
            super().on_coalesced_dispatch(rids, dispatch)
        except InjectedEngineError:
            self.clock.advance(self.fault_wall_s)
            raise


@pytest.mark.parametrize("seed", SEEDS)
def test_healthy_request_admitted_after_poison_storm(seed):
    # Regression: the admission estimator's service-time EMA must not be
    # poisoned by quarantine/bisection incidents.  The pre-fix step() gate
    # excluded only TIMEOUT/CRASHED, so a quarantine-bearing step fed its
    # fault-handling wall (here 900 s per failed bisection sub-dispatch)
    # into the EMA — inflating it past any default deadline and shedding
    # every later healthy request as overload, permanently: a shed request
    # never runs, so nothing ever corrects the estimate back down.
    clock = VirtualClock()
    monkey = _SlowFaultMonkey(
        ChaosConfig(seed=seed, fault_rate=0.25, classes=("poison_lane",)),
        clock, fault_wall_s=900.0)
    srv = StudyServer(ServeConfig(coalesce=True, audit_fraction=1.0,
                                  seed=seed),
                      clock=clock, chaos=monkey)
    for _ in range(8):
        srv.submit(SPEC_A, deadline_s=1e9)
    out = srv.drain()
    assert any(r.status == QUARANTINED for r in out)  # a real storm
    # A healthy follow-up at the DEFAULT deadline (300 s << the storm's
    # accumulated bisection wall) must be admitted and served.
    monkey.exempt.add(8)
    rid = srv.submit(SPEC_A)
    assert isinstance(rid, int), f"healthy follow-up shed: {rid}"
    (resp,) = srv.drain()
    assert resp.status == OK
    # ...and the now-observed healthy service time keeps admitting.
    monkey.exempt.add(9)
    rid2 = srv.submit(SPEC_A)
    assert isinstance(rid2, int), f"second follow-up shed: {rid2}"
    (resp2,) = srv.drain()
    assert resp2.status == OK


# -- blessed widths: warm manifest + compile-key reuse -----------------------


def test_blessed_width_warm_entries_and_zero_new_compiles(tmp_path):
    cfg = ServeConfig(cache_dir=str(tmp_path), default_deadline_s=1e9,
                      coalesce=True, audit_fraction=0.0)
    srv = StudyServer(cfg, clock=VirtualClock())
    for _ in range(3):  # 3 lanes -> blessed width 4
        srv.submit(SPEC_A)
    assert all(r.status == OK for r in srv.drain())
    entries = srv.warm.load_manifest()
    assert {e["lanes"] for e in entries} == {4}
    assert all(e["lanes"] in BLESSED_LANE_WIDTHS for e in entries)

    # Process death: in-process jit caches vanish; manifest + persistent
    # compile cache survive.  The restarted server re-warms the blessed
    # widths and re-serves the same coalesced shape with zero new scan
    # compiles.
    _engine._sweep_fn.cache_clear()
    srv2, replayed = restart_server(cfg, clock=VirtualClock())
    assert replayed == []
    before = dict(_engine.sweep_cache_sizes())
    for _ in range(3):
        srv2.submit(SPEC_A)
    out = srv2.drain()
    after = dict(_engine.sweep_cache_sizes())
    assert all(r.status == OK and r.engine == "coalesced" for r in out)
    assert after == before  # blessed-width keys were all re-warmed


# -- deadline accounting at admission ----------------------------------------


def test_request_that_would_expire_while_queued_sheds_at_admission():
    srv = _server(coalesce=False)
    srv._service_ema = 10.0  # measured: ~10 s of service per request
    assert isinstance(srv.submit(SPEC_A, deadline_s=1e9), int)
    # Two requests ahead -> ~30 s to completion; a 5 s deadline cannot be
    # met, so the request sheds now instead of timing out after dispatch.
    resp = srv.submit(SPEC_A, deadline_s=5.0)
    assert resp.status == "rejected_overload"
    assert "would expire while queued" in resp.error
    # A deadline the queue can meet is admitted.
    assert isinstance(srv.submit(SPEC_A, deadline_s=60.0), int)


# -- persistence hardening (schema versions + quarantine-and-rebuild) --------


def test_corrupt_warm_manifest_quarantined_not_wedging_restart(tmp_path):
    cfg = ServeConfig(cache_dir=str(tmp_path), default_deadline_s=1e9)
    srv = StudyServer(cfg, clock=VirtualClock())
    srv.submit(SPEC_A)
    assert srv.drain()[0].status == OK
    manifest = srv.warm.manifest_path
    manifest.write_text(manifest.read_text()[:40])  # torn write

    srv2, replayed = restart_server(cfg, clock=VirtualClock())
    assert replayed == []
    assert srv2.warm.quarantined_manifests == 1
    assert (tmp_path / "warm_manifest.json.corrupt-0").exists()
    assert not manifest.exists()  # rebuilt from empty on next record
    assert srv2.submit(SPEC_A) == 0 or True
    assert srv2.drain()[0].status == OK
    assert len(srv2.warm.load_manifest()) == 2  # rebuilt


def test_wrong_manifest_schema_version_quarantined(tmp_path):
    cfg = ServeConfig(cache_dir=str(tmp_path), default_deadline_s=1e9)
    srv = StudyServer(cfg, clock=VirtualClock())
    srv.warm.manifest_path.write_text(json.dumps(
        {"schema_version": 999, "entries": []}))
    assert srv.warm.load_manifest() == []
    assert srv.warm.quarantined_manifests == 1


def test_corrupt_journal_quarantined_not_wedging_restart(tmp_path):
    cfg = ServeConfig(cache_dir=str(tmp_path), default_deadline_s=1e9)
    (tmp_path / "journal.json").write_text('{"next_rid": 3, "inflight"')
    srv, replayed = restart_server(cfg, clock=VirtualClock())
    assert replayed == []
    assert srv.stats["quarantined_journals"] == 1
    assert (tmp_path / "journal.json.corrupt-0").exists()
    assert isinstance(srv.submit(SPEC_A), int)
    assert srv.drain()[0].status == OK


def test_resultset_load_json_raises_named_schema_errors(tmp_path):
    rs = build_study(SPEC_A).run("sequential")
    path = rs.save_json(tmp_path / "rs.json")
    loaded = ResultSet.load_json(path)
    _assert_rows_equal(loaded, rs)

    torn = tmp_path / "torn.json"
    torn.write_text(path.read_text()[:25])
    with pytest.raises(ResultSetSchemaError, match="truncated or corrupt"):
        ResultSet.load_json(torn)

    payload = json.loads(path.read_text())
    payload["schema_version"] = 999
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(payload))
    with pytest.raises(ResultSetSchemaError, match="schema_version"):
        ResultSet.load_json(bad)

    # Pre-stamp goldens (no version field) are version 1: must load.
    payload = json.loads(path.read_text())
    del payload["schema_version"]
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps(payload))
    _assert_rows_equal(ResultSet.load_json(legacy), rs)

    mangled = tmp_path / "mangled.json"
    payload = json.loads(path.read_text())
    del payload["points"][0]["results"]
    mangled.write_text(json.dumps(payload))
    with pytest.raises(ResultSetSchemaError, match="malformed"):
        ResultSet.load_json(mangled)
