"""Unified ``Study`` API: spec validation, the execution planner's
compile-budget prediction, cross-engine bit-exactness, and the
``ResultSet`` container.

The planner's numerics are additionally pinned by the long-standing
cross-engine harnesses — ``tests/test_batch_engine.py`` (bit-exact vs
sequential ``run_all`` on the full fleet) and
``tests/golden/fig7_batched_golden.json`` hold the redesign to the
pre-study numbers field-for-field."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    HWParams,
    LazyPIMConfig,
    ResultSet,
    SignatureSpec,
    Study,
    grid,
    run_all,
    sweep_cache_sizes,
    workload,
)
from repro.core.mechanisms import finalize_result
from repro.sim.costmodel import hw_leaf_dtypes
from repro.sim.engine import stack_hw, stack_lazy
from repro.sim.prep import prepare
from repro.sim.trace import make_trace

SMALL = dict(num_kernels=3, windows_per_kernel=2)


def _small_study(**kw):
    kw.setdefault("workloads", [workload("pagerank", "arxiv", scale=0.4, **SMALL),
                                workload("htap128", scale=0.004, **SMALL)])
    kw.setdefault("mechanisms", ("cpu", "cg", "lazypim"))
    return Study(**kw)


def _assert_equal(a, b, label):
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    for k in da:
        assert da[k] == db[k], f"{label}: field {k}: {da[k]} != {db[k]}"


# ---------------------------------------------------------------------------
# Spec validation: every bad entry fails at construction, named
# ---------------------------------------------------------------------------


def test_unknown_workload_name_rejected():
    with pytest.raises(ValueError, match=r"workloads\[1\].*'nosuch-arxiv'"):
        Study(workloads=["htap128", "nosuch-arxiv"])


def test_graph_app_without_input_rejected():
    with pytest.raises(ValueError, match=r"workloads\[0\].*needs a graph input"):
        Study(workloads=["pagerank"])


def test_table_app_with_graph_rejected():
    with pytest.raises(ValueError, match=r"workloads\[0\].*table workload"):
        Study(workloads=[("htap128", "enron")])


def test_unknown_mechanism_rejected():
    with pytest.raises(ValueError, match=r"mechanisms\[1\].*'warp'"):
        Study(workloads=["htap128"], mechanisms=("cpu", "warp"))


def test_mismatched_hw_list_rejected():
    with pytest.raises(ValueError, match=r"hw list length 1 != 2 workloads"):
        Study(workloads=["htap128", ("pagerank", "arxiv")], hw=[HWParams()])


def test_mixed_static_lazy_flags_rejected():
    with pytest.raises(ValueError, match=r"lazy\[1\].*partial_commits"):
        Study(workloads=["htap128"],
              lazy=[LazyPIMConfig(), LazyPIMConfig(partial_commits=False)])
    with pytest.raises(ValueError, match=r"lazy\[2\].*max_rollbacks"):
        Study(workloads=["htap128"],
              lazy=[LazyPIMConfig(), LazyPIMConfig(dbi_interval_cycles=3200.0),
                    LazyPIMConfig(max_rollbacks=5)])


def test_grid_unknown_field_rejected():
    with pytest.raises(ValueError, match=r"unknown HWParams field 'warp_size'"):
        grid(warp_size=[16, 32])


# ---------------------------------------------------------------------------
# Planner: predicted compile budget vs measured jit-cache deltas
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hw_grid_study():
    """A fig8-style hw-grid study (one workload x 3 bandwidth points x 2
    DBI settings) plus the measured compile deltas of its batched run."""
    study = _small_study(hw=grid(offchip_bw_gbs=[16.0, 32.0, 64.0]),
                         lazy=[LazyPIMConfig(use_dbi=True),
                               LazyPIMConfig(use_dbi=False)])
    plan = study.plan()
    before = sweep_cache_sizes()
    results = study.run()
    after = sweep_cache_sizes()
    deltas = {m: after[m] - before[m] for m in study.mechanisms}
    return study, plan, results, deltas


def test_plan_shape(hw_grid_study):
    study, plan, results, _ = hw_grid_study
    assert plan.num_points == 2 * 3 * 2 == len(results.points)
    assert plan.num_buckets == 2  # pagerank-arxiv and htap128 buckets
    assert plan.compiles_per_mechanism == {m: 2 for m in study.mechanisms}
    assert plan.total_compiles == 6
    assert sum(b["lanes"] for b in plan.buckets) == plan.num_points
    assert "geometry buckets" in plan.describe()


def test_measured_compiles_within_plan(hw_grid_study):
    """At most one measured XLA compile per (mechanism, bucket), whatever
    the hw x lazy cross-product size — the acceptance form of the study
    compile budget (exact cold-cache equality is asserted in a fresh
    process by ``benchmarks/check_budget.py --live``)."""
    _, plan, _, deltas = hw_grid_study
    for m, d in deltas.items():
        assert d <= plan.compiles_per_mechanism[m], (m, d, plan.buckets)


def test_grid_points_cross_product_order():
    g = grid(offchip_bw_gbs=[16.0, 32.0], pim_cores=[8, 16])
    pts = g.points()
    assert [(p.offchip_bw_gbs, p.pim_cores) for p in pts] == \
        [(16.0, 8), (16.0, 16), (32.0, 8), (32.0, 16)]
    assert g.labels()[2] == {"offchip_bw_gbs": 32.0, "pim_cores": 8}


# ---------------------------------------------------------------------------
# Cross-engine bit-exactness of the folded hw/lazy axes
# ---------------------------------------------------------------------------


def test_batched_study_bit_exact_vs_sequential(hw_grid_study):
    """The planner folds hw and lazy points onto the stacked lane axis; the
    results must equal the per-point sequential reference on every
    ``SimResult`` field."""
    study, _, results, _ = hw_grid_study
    seq = study.run(engine="sequential")
    assert len(results.points) == len(seq.points)
    for bp, sp in zip(results.points, seq.points):
        assert (bp.workload, bp.hw_index, bp.lazy_index) == \
            (sp.workload, sp.hw_index, sp.lazy_index)
        for m in study.mechanisms:
            _assert_equal(sp.results[m], bp.results[m],
                          f"{bp.workload}/hw{bp.hw_index}/lz{bp.lazy_index}/{m}")


def test_zipped_hw_list_matches_sequential():
    wls = [workload("pagerank", "arxiv", threads=t, scale=0.4, **SMALL)
           for t in (4, 16)]
    hws = [HWParams(cpu_cores=t, pim_cores=t) for t in (4, 16)]
    study = Study(workloads=wls, hw=hws, mechanisms=("cpu", "lazypim"))
    rs = study.run()
    for i, p in enumerate(rs.points):
        assert p.hw_index == i and p.hw is hws[i]
        seq = run_all(study.traces()[i], hws[i], ("cpu", "lazypim"))
        for m in ("cpu", "lazypim"):
            _assert_equal(seq[m], p.results[m], f"zipped[{i}]/{m}")


def test_prepared_traces_and_per_entry_spec():
    tt = prepare(make_trace("pagerank", "arxiv", scale=0.4, **SMALL))
    rs = Study(workloads=[tt], mechanisms=("cpu",)).run()
    assert rs.points[0].workload == "pagerank-arxiv"
    spec = SignatureSpec(sig_bits=4096)
    study = Study(workloads=[workload("htap128", spec=spec, scale=0.004,
                                      **SMALL)], mechanisms=("lazypim",))
    assert study.traces()[0].spec == spec
    _assert_equal(run_all(study.traces()[0], HWParams(),
                          ("lazypim",))["lazypim"],
                  study.run().points[0].results["lazypim"], "spec-override")


def test_unknown_engine_rejected():
    with pytest.raises(ValueError, match="unknown engine 'warp'"):
        _small_study().run(engine="warp")


# ---------------------------------------------------------------------------
# ResultSet container
# ---------------------------------------------------------------------------


def test_resultset_rows_pivot_normalized(hw_grid_study):
    study, _, results, _ = hw_grid_study
    rows = results.to_rows()
    assert len(rows) == len(results.points) * len(study.mechanisms)
    assert {r["mechanism"] for r in rows} == set(study.mechanisms)
    # normalized ratios ride along when a cpu baseline is present
    assert all(r["speedup"] == 1.0 for r in rows if r["mechanism"] == "cpu")
    table = results.pivot(("workload", "hw_index", "lazy_index"),
                          "mechanism", "speedup")
    assert len(table) == len(results.points)
    norm = results.normalized()
    for p, s in zip(results.points, norm):
        key = (p.workload, p.hw_index, p.lazy_index)
        assert table[key]["lazypim"] == s["lazypim"]["speedup"]
    # a collapsed pivot with colliding cells fails loudly
    with pytest.raises(ValueError, match="duplicate cell"):
        results.pivot("workload", "mechanism", "speedup")


def test_normalized_requires_baseline():
    rs = _small_study(mechanisms=("lazypim",)).run()
    with pytest.raises(ValueError, match="needs 'cpu'"):
        rs.normalized()


def test_resultset_save_load_round_trip(tmp_path, hw_grid_study):
    _, _, results, _ = hw_grid_study
    path = results.save_json(tmp_path / "rs.json")
    loaded = ResultSet.load_json(path)
    assert loaded.mechanisms == results.mechanisms
    assert len(loaded.points) == len(results.points)
    for a, b in zip(results.points, loaded.points):
        assert (a.workload, a.hw_index, a.lazy_index) == \
            (b.workload, b.hw_index, b.lazy_index)
        assert a.hw == b.hw and a.lazy == b.lazy
        for m in a.results:
            _assert_equal(a.results[m], b.results[m], f"reload/{m}")


def test_resultset_concat(hw_grid_study):
    _, _, results, _ = hw_grid_study
    both = ResultSet.concat([results, results])
    assert len(both) == 2 * len(results)
    assert both.mechanisms == results.mechanisms


# ---------------------------------------------------------------------------
# Stacking helpers: declared dtypes and static-flag discipline
# ---------------------------------------------------------------------------


def test_stack_hw_round_trips_every_field_at_declared_dtype():
    """Satellite contract: every HWParams field survives stack_hw at the
    dtype declared in ``costmodel.hw_leaf_dtypes`` — including int-valued
    floats (``offchip_bw_gbs=16`` vs ``16.0`` must share a compile key)."""
    import typing

    from repro.sim.costmodel import _HW_INT_FIELDS

    # the explicit map must track the real field annotations: a new int
    # field missing from _HW_INT_FIELDS would silently stack as float32
    # (lossy past 2**24), so drift fails here rather than in a sweep
    hints = typing.get_type_hints(HWParams)
    assert {n for n, t in hints.items() if t is int} == set(_HW_INT_FIELDS)
    dtypes = hw_leaf_dtypes()
    a = HWParams()
    b = HWParams(offchip_bw_gbs=16, cpu_cores=8, freq_ghz=2.5, nc_bytes=64)
    stacked = stack_hw([a, b])
    assert set(dtypes) == {f.name for f in dataclasses.fields(HWParams)}
    for name, dt in dtypes.items():
        leaf = getattr(stacked, name)
        assert leaf.shape == (2,) and leaf.dtype == dt, name
        np.testing.assert_allclose(
            np.asarray(leaf),
            np.asarray([getattr(a, name), getattr(b, name)], dtype=dt),
            rtol=0, atol=0, err_msg=name)
    # int-typed python values land on the float leaves losslessly
    assert float(stacked.offchip_bw_gbs[1]) == 16.0
    assert stacked.offchip_bw_gbs.dtype == jnp.float32


def test_stack_lazy_stacks_traced_knobs_and_rejects_static_mix():
    cfgs = [LazyPIMConfig(dbi_interval_cycles=1600.0),
            LazyPIMConfig(dbi_interval_cycles=3200.0, use_dbi=False)]
    s = stack_lazy(cfgs)
    assert s.partial_commits is True and s.cpuws_regs == 16
    np.testing.assert_array_equal(np.asarray(s.dbi_interval_cycles),
                                  np.asarray([1600.0, 3200.0], np.float32))
    np.testing.assert_array_equal(np.asarray(s.use_dbi),
                                  np.asarray([True, False]))
    with pytest.raises(ValueError, match=r"\[1\].*partial_commits"):
        stack_lazy([LazyPIMConfig(), LazyPIMConfig(partial_commits=False)])


def test_finalize_result_is_the_single_constructor():
    """Satellite contract: every engine funnels accumulators through
    ``finalize_result`` — spot-check it against a sequential result."""
    tt = prepare(make_trace("pagerank", "arxiv", scale=0.4, **SMALL))
    r = run_all(tt, HWParams(), ("cg",))["cg"]
    rebuilt = finalize_result(tt.name, "cg", {
        k: getattr(r, k) for k in (
            "time_ns", "offchip_bytes", "dram_bytes", "l1_accesses",
            "l2_accesses", "flush_lines", "blocked_accesses")})
    assert rebuilt.name == r.name and rebuilt.time_ns == r.time_ns
