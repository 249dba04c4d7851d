"""Mesh-sharded lane dispatch: routing/padding policy, bit-exactness of
the sharded planner vs the byte-identical single-device reference (all 6
mechanisms x buckets x partial/full-commit, non-divisible lane counts),
mesh-transparent coalesced serve storms, and warm-manifest device
dimensioning ("rebuild, not wedge" on a device-count mismatch).

Multi-device legs run on simulated CPU devices::

    XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=4 \\
        PYTHONPATH=src python -m pytest tests/test_mesh_dispatch.py

``repro.sim.mesh`` translates that env var into ``XLA_FLAGS`` at first
import — which this module performs before anything can touch a jax
device — so the policy tests below run everywhere and the differential
tests skip themselves on single-device hosts.
"""

import dataclasses
import os
import types

import numpy as np
import pytest

from repro.sim import mesh  # noqa: F401  (env translation precedes jax init)

from repro.core.coherence import LazyPIMConfig
from repro.launch import mesh as launch_mesh
from repro.serve import (
    OK,
    QUARANTINED,
    ChaosConfig,
    ChaosMonkey,
    ServeConfig,
    StudyServer,
    VirtualClock,
)
from repro.serve.warm import WarmCache, study_warm_entries
from repro.sim import engine as _engine
from repro.sim.study import Study, grid, workload

SEEDS = ([int(os.environ["REPRO_CHAOS_SEED"])]
         if "REPRO_CHAOS_SEED" in os.environ else [0, 1, 2])

DEVICES = mesh.available_devices()
multi_device = pytest.mark.skipif(
    DEVICES < 2,
    reason="needs >= 2 devices "
           "(set XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT)")

SMALL = dict(scale=0.4, num_kernels=3, windows_per_kernel=2)
SPEC_A = {
    "workloads": [{"app": "pagerank", "graph": "arxiv", **SMALL}],
    "mechanisms": ["cpu", "lazypim"],
    "threads": 16,
}


def _study(partial_commits=True, hw_points=3):
    """Two geometry buckets x ``hw_points`` lanes each, every mechanism —
    lane counts deliberately NOT multiples of any mesh size > 1."""
    return Study(
        workloads=[workload("pagerank", "arxiv", **SMALL),
                   workload("htap128", scale=0.004, num_kernels=3,
                            windows_per_kernel=2)],
        hw=grid(offchip_bw_gbs=[float(16 * 2 ** i)
                                for i in range(hw_points)]),
        mechanisms=_engine.MECHANISMS,
        lazy=LazyPIMConfig(partial_commits=partial_commits))


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


# -- routing / padding policy (device-count independent) ---------------------


def test_devices_for_routes_to_largest_pow2_subset():
    assert [mesh.devices_for(n, 4) for n in (1, 2, 3, 4, 5, 8)] == \
        [1, 2, 2, 4, 4, 4]
    assert mesh.devices_for(64, 2) == 2
    assert mesh.devices_for(3, 1) == 1
    with pytest.raises(ValueError):
        mesh.devices_for(0, 4)


def test_mesh_lane_width_rounds_up_to_mesh_multiple():
    assert [mesh.mesh_lane_width(n, 4) for n in (1, 3, 4, 5, 8)] == \
        [4, 4, 4, 8, 8]
    assert mesh.mesh_lane_width(5, 1) == 5  # single device: no padding
    with pytest.raises(ValueError):
        mesh.mesh_lane_width(5, 0)


def test_resolve_devices_bounds():
    assert mesh.resolve_devices(None) == DEVICES
    assert mesh.resolve_devices(1) == 1
    with pytest.raises(ValueError):
        mesh.resolve_devices(0)
    with pytest.raises(ValueError):
        mesh.resolve_devices(DEVICES + 1)


def test_blessed_widths_compose_with_mesh_sizes():
    from repro.serve import BLESSED_LANE_WIDTHS, blessed_width

    # Blessed widths stay the compile-key space: the mesh multiple is
    # always chosen FROM them, and every blessed width >= a pow2 mesh size
    # divides evenly by it.
    assert blessed_width(3, 2) == 4
    assert blessed_width(1, 2) == 2
    assert blessed_width(5, 4) == 8
    assert blessed_width(3) == blessed_width(3, 1) == 4
    for d in (1, 2, 4, 8):
        for n in range(1, BLESSED_LANE_WIDTHS[-1] + 1):
            w = blessed_width(n, d)
            assert w in BLESSED_LANE_WIDTHS and w >= n and w % d == 0
    with pytest.raises(ValueError):
        blessed_width(0, 2)
    with pytest.raises(ValueError):
        blessed_width(BLESSED_LANE_WIDTHS[-1], BLESSED_LANE_WIDTHS[-1] * 2)


def test_single_device_path_is_the_same_function_object():
    # devices=1 must select THE pre-mesh jitted callables, not equivalents
    # — that is what "byte-identical fallback" means, and it keeps one
    # shared compile counter however the caller spells "one device".
    for m in _engine.MECHANISMS:
        assert _engine._sweep_fn_mesh(m, 1) is _engine._sweep_fn(m)
    # ...and it must track cache_clear (the tests' process-death stub).
    fn = _engine._sweep_fn("cpu")
    _engine._sweep_fn.cache_clear()
    assert _engine._sweep_fn_mesh("cpu", 1) is not fn


def test_rules_for_fsdp_pod_flag():
    single = types.SimpleNamespace(axis_names=("data", "model"))
    multi = types.SimpleNamespace(axis_names=("pod", "data", "model"))
    assert launch_mesh.rules_for(single) is launch_mesh.LOGICAL_RULES_SINGLE
    assert launch_mesh.rules_for(multi) is launch_mesh.LOGICAL_RULES_MULTI
    assert launch_mesh.rules_for(multi, fsdp_pod=True) \
        is launch_mesh.LOGICAL_RULES_MULTI_FSDP_POD
    assert launch_mesh.LOGICAL_RULES_MULTI_FSDP_POD["embed"] == \
        ("pod", "data")
    with pytest.raises(ValueError, match="multi-pod"):
        launch_mesh.rules_for(single, fsdp_pod=True)


def test_sequential_engine_rejects_multi_device():
    st = Study(workloads=[workload("pagerank", "arxiv", **SMALL)],
               mechanisms=("cpu",))
    with pytest.raises(ValueError, match="sequential"):
        st.run(engine="sequential", devices=2)


def test_plan_predicts_device_routing_and_padding():
    plan = _study().plan(devices=1)
    assert plan.devices == 1
    assert all(b["devices"] == 1 and b["padded_lanes"] == b["lanes"]
               for b in plan.buckets)
    if DEVICES >= 2:
        plan = _study().plan()  # None = every visible device
        assert plan.devices == DEVICES
        for b in plan.buckets:
            assert b["devices"] == mesh.devices_for(b["lanes"], DEVICES)
            assert b["padded_lanes"] % b["devices"] == 0
            assert b["padded_lanes"] >= b["lanes"]
        # The compile budget is device-count independent: one compile per
        # (mechanism, bucket) whichever mesh variant it lands in.
        assert plan.compiles_per_mechanism == \
            _study().plan(devices=1).compiles_per_mechanism


# -- differential: sharded vs single-device, bit-exact -----------------------


@multi_device
@pytest.mark.parametrize("partial_commits", [True, False])
def test_sharded_study_bit_exact_with_single_device(partial_commits):
    # 3 lanes per bucket over 2/4 devices: every dispatch pads (mesh
    # padding in the planner, not the coalescer) and every SimResult field
    # of every mechanism/bucket/lane must match the single-device rows.
    ref = _study(partial_commits).run(devices=1)
    sharded = _study(partial_commits).run()  # None -> all visible devices
    _assert_rows_equal(ref, sharded)


@multi_device
def test_sharded_compile_count_matches_plan_prediction():
    # Use a geometry no other test hits so the measured delta is this
    # run's own compiles (lru caches persist across tests in-process).
    st = Study(workloads=[workload("pagerank", "arxiv", scale=0.4,
                                   num_kernels=4, windows_per_kernel=3)],
               hw=grid(offchip_bw_gbs=[16.0, 32.0, 64.0, 96.0, 128.0]),
               mechanisms=("cpu", "lazypim"))
    plan = st.plan()
    before = _engine.sweep_cache_sizes(st.mechanisms)
    st.run()
    after = _engine.sweep_cache_sizes(st.mechanisms)
    measured = {m: after[m] - before[m] for m in st.mechanisms}
    assert measured == plan.compiles_per_mechanism


@multi_device
def test_mesh_pad_lanes_never_contribute():
    # 5 lanes on >= 2 devices pads at least one all-sentinel lane; a
    # 1-lane study shares no padding at all.  Both must equal their
    # unsharded runs field-exactly — the pads' carry passthrough
    # contributes nothing to any real lane.
    for hw_points in (1, 5):
        st = lambda: Study(  # noqa: E731
            workloads=[workload("pagerank", "arxiv", **SMALL)],
            hw=grid(offchip_bw_gbs=[float(16 + 8 * i)
                                    for i in range(hw_points)]),
            mechanisms=_engine.MECHANISMS)
        _assert_rows_equal(st().run(devices=1), st().run())


# -- mesh-transparent serve (coalesced storms on a 2-device mesh) ------------


def _storm(seed, devices):
    clock = VirtualClock()
    monkey = ChaosMonkey(ChaosConfig(seed=seed, fault_rate=0.25,
                                     classes=("poison_lane",)), clock=clock)
    srv = StudyServer(ServeConfig(default_deadline_s=1e9, coalesce=True,
                                  audit_fraction=1.0, seed=seed,
                                  devices=devices),
                      clock=clock, chaos=monkey)
    for _ in range(8):
        srv.submit(SPEC_A)
    return srv, srv.drain()


@multi_device
@pytest.mark.parametrize("seed", SEEDS)
def test_coalesced_storm_is_mesh_transparent(seed):
    # Bisection, quarantine and the sequential audit are lane-slice logic;
    # sharding the dispatch must not change a single decision or number.
    ref_srv, ref_out = _storm(seed, devices=1)
    mesh_srv, mesh_out = _storm(seed, devices=2)
    assert [(r.rid, r.status) for r in ref_out] == \
        [(r.rid, r.status) for r in mesh_out]
    assert set(ref_srv.quarantine) == set(mesh_srv.quarantine)
    assert ref_srv.stats["bisections"] == mesh_srv.stats["bisections"]
    assert ref_srv.stats["audit_lanes"] == mesh_srv.stats["audit_lanes"]
    for a, b in zip(ref_out, mesh_out):
        if a.status == OK:
            _assert_rows_equal(a.results, b.results)
        else:
            assert a.status == QUARANTINED


# -- warm manifest: the device-count dimension --------------------------------


def test_warm_entries_record_mesh_routing():
    st = Study(workloads=[workload("pagerank", "arxiv", **SMALL)],
               hw=grid(offchip_bw_gbs=[16.0, 32.0, 64.0]),
               mechanisms=("cpu", "lazypim"))
    for e in study_warm_entries(st):
        assert e["devices"] == 1 and e["lanes"] == 3
    if DEVICES >= 2:
        for e in study_warm_entries(st, devices=DEVICES):
            assert e["devices"] == mesh.devices_for(3, DEVICES)
            assert e["lanes"] % e["devices"] == 0


def test_warm_entries_are_the_plan_rows():
    # Warm rows read the plan: for a two-bucket study, one row per
    # (bucket, mechanism) at the geometry the bucket's lanes are padded to,
    # the lane count and mesh size the bucket routes to, and the study's
    # spec and static flags — at one device and at every visible one.
    st = _study(hw_points=3)
    bls = st.bucket_lanes()
    assert len(bls) == 2
    static = _engine.lazy_static(st.lazy_points()[0])
    for devices in sorted({1, DEVICES}):
        plan = st.plan(devices)
        want = []
        for b, bl in zip(plan.buckets, bls):
            d = mesh.devices_for(len(bl.traces), devices)
            assert (b["devices"], b["padded_lanes"]) == (
                d, mesh.mesh_lane_width(len(bl.traces), d))
            want += [{**bl.shape, "mechanism": m, "lanes": b["padded_lanes"],
                      "devices": d,
                      "spec": dataclasses.asdict(bl.traces[0].spec),
                      "lazy_static": static} for m in st.mechanisms]
        assert study_warm_entries(st, devices=devices) == want


def test_warm_replay_skips_overwide_mesh_entries(tmp_path):
    # A manifest carried over from a bigger host: entries recorded on a
    # wider mesh than this host has are skipped and counted — the restart
    # rebuilds its own compile keys from live traffic, it never wedges.
    st = Study(workloads=[workload("pagerank", "arxiv", **SMALL)],
               mechanisms=("cpu",))
    st.traces()
    entries = study_warm_entries(st)
    legacy = {k: v for k, v in entries[0].items() if k != "devices"}
    overwide = dict(entries[0], devices=64)  # wider than any CI leg
    wc = WarmCache(tmp_path)
    assert wc.record_entries(entries + [legacy, overwide]) == 3
    #      ^ the legacy (pre-mesh, no devices key) row is a distinct
    #        manifest key and must still load, replaying at 1 device
    replayed = wc.warm_from_manifest()
    assert replayed == 2  # the devices=1 entry + the legacy row
    assert wc.skipped_entries == 1


def test_serve_config_devices_validated_at_boot():
    with pytest.raises(ValueError, match="devices"):
        StudyServer(ServeConfig(devices=DEVICES + 1), clock=VirtualClock())


@multi_device
def test_mesh_server_healthy_coalesced_group_bit_exact(tmp_path):
    # The CLI-smoke shape: healthy coalesced traffic on a mesh server,
    # manifest rows carry the routed device count, and a single-device
    # server serves the identical bytes.
    def _serve(devices, cache):
        srv = StudyServer(ServeConfig(default_deadline_s=1e9, coalesce=True,
                                      audit_fraction=0.0, devices=devices,
                                      cache_dir=cache),
                          clock=VirtualClock())
        for _ in range(3):  # 3 lanes -> blessed width 4, mesh multiple
            srv.submit(SPEC_A)
        return srv, srv.drain()

    srv1, out1 = _serve(1, str(tmp_path / "one"))
    srv2, out2 = _serve(2, str(tmp_path / "two"))
    assert all(r.status == OK and r.engine == "coalesced"
               for r in out1 + out2)
    for a, b in zip(out1, out2):
        _assert_rows_equal(a.results, b.results)
    assert {e["devices"] for e in srv1.warm.load_manifest()} == {1}
    assert {e["devices"] for e in srv2.warm.load_manifest()} == {2}
    assert {e["lanes"] for e in srv2.warm.load_manifest()} == {4}


def test_dispatch_devices_reported_to_boundary():
    seen = []

    def spy(info, thunk):
        seen.append((info.mechanism, info.lanes, info.devices))
        return thunk()

    st = Study(workloads=[workload("pagerank", "arxiv", **SMALL)],
               hw=grid(offchip_bw_gbs=[16.0, 32.0, 64.0]),
               mechanisms=("cpu",))
    st.run(on_dispatch=spy, devices=1)
    assert seen == [("cpu", 3, 1)]
    if DEVICES >= 2:
        seen.clear()
        st.run(on_dispatch=spy)
        (d,) = {s[2] for s in seen}
        assert d == mesh.devices_for(3, DEVICES)
