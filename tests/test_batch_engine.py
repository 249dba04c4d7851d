"""Cross-engine equivalence harness for the geometry-bucketed batch engine.

The ``Study`` planner (``repro.sim.study``) pads the whole workload fleet
onto a few geometry buckets (``repro.sim.prep.bucket_traces``) and runs one
compiled, vmapped window scan per (mechanism, bucket).  Padding bugs would silently
corrupt fleet averages, so the contract is *bit-exactness*: batched results
must equal sequential ``run_all`` results on **every** ``SimResult`` field,
for every workload in the full extended fleet (22 workloads), every
mechanism, and both LazyPIM commit ablations — plus a measured compile
budget (at most one XLA compile per (mechanism, bucket)) and the
bucket-boundary edge cases (a trace sitting exactly at its bucket bound, a
singleton bucket, and the ``stack_traces`` geometry rejection that bucketing
routes around).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coherence import LazyPIMConfig, simulate_lazypim
from repro.core.signatures import hash_positions
from repro.sim import prep as P
from repro.sim.costmodel import HWParams
from repro.sim.engine import (
    MECHANISMS,
    lanes_per_step,
    run_all,
    stack_traces,
    sweep_cache_sizes,
)
from repro.sim.prep import bucket_bound, bucket_traces, pad_trace, prepare
from repro.sim.study import Study
from repro.sim.trace import all_workloads, make_trace

HW = HWParams()

# The full fig7 suite must fit in ≤ 1 measured compile per (mechanism,
# bucket) with at most 3 buckets — the structural form of the 18-compile
# fleet budget (authoritative constant: benchmarks/check_budget.py, which
# also gates the committed BENCH_engine.json record in CI; before
# bucketing the suite cost one compile per workload × mechanism = 132).
MAX_FLEET_BUCKETS = 3


def _batch(tts, **kw):
    """One ``{mechanism: SimResult}`` dict per trace, in input order, from
    the planner's batched engine."""
    return [p.results for p in Study(workloads=tts, hw=HW, **kw).run().points]


def _assert_equal(a, b, label):
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    for k in da:
        assert da[k] == db[k], f"{label}: field {k}: batch={db[k]} seq={da[k]}"


# ---------------------------------------------------------------------------
# Full-fleet differential: 22 workloads × 6 mechanisms × both ablations
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet():
    return [prepare(make_trace(app, g, threads=16))
            for app, g in all_workloads(extended=True)]


@pytest.fixture(scope="module")
def batched(fleet):
    """Batched fleet results plus the compile-count deltas of the run."""
    before = sweep_cache_sizes()
    results = _batch(fleet)
    after = sweep_cache_sizes()
    return results, {m: after[m] - before[m] for m in after}


def test_fleet_buckets_and_compile_budget(fleet, batched):
    _, deltas = batched
    buckets = bucket_traces(fleet)
    # the 7 fleet geometries collapse to a handful of pow2-ish buckets
    assert len(buckets) <= MAX_FLEET_BUCKETS
    assert {i for idx, _ in buckets for i in idx} == set(range(len(fleet)))
    # at most ONE measured XLA compile per (mechanism, bucket) — with the
    # bucket cap above this bounds the fleet at 6 × 3 = 18 compiles
    for m, d in deltas.items():
        assert d <= len(buckets), f"{m}: {d} compiles for {len(buckets)} buckets"
    assert sum(deltas.values()) <= len(MECHANISMS) * MAX_FLEET_BUCKETS


@pytest.mark.parametrize("mechanism", MECHANISMS)
def test_batch_bit_exact_full_fleet(fleet, batched, mechanism):
    """The batched planner == sequential run_all on every SimResult field, every
    workload (the compiled scans are shared module-wide, so this enumerates
    comparisons, not recompiles)."""
    results, _ = batched
    for tt, br in zip(fleet, results):
        seq = run_all(tt, HW, mechanisms=(mechanism,))[mechanism]
        _assert_equal(seq, br[mechanism], f"{tt.name}/{mechanism}")


def test_batch_bit_exact_full_commit_ablation(fleet):
    """The fig12 ablation (partial_commits=False) changes the LazyPIM
    dataflow (accumulate-across-windows); the batched path must track it
    bit-exactly too."""
    cfg = LazyPIMConfig(partial_commits=False)
    results = _batch(fleet, mechanisms=("lazypim",), lazy=cfg)
    for tt, br in zip(fleet, results):
        seq = simulate_lazypim(tt, HW, cfg)
        _assert_equal(seq, br["lazypim"], f"{tt.name}/lazypim-fullcommit")


def test_batch_results_keep_workload_names(fleet, batched):
    results, _ = batched
    for tt, br in zip(fleet, results):
        for m, r in br.items():
            assert r.name == tt.name and r.mechanism == m


# ---------------------------------------------------------------------------
# Bucket-boundary edge cases (small traces)
# ---------------------------------------------------------------------------


def _small(app, graph, **kw):
    kw.setdefault("threads", 16)
    kw.setdefault("num_kernels", 3)
    kw.setdefault("windows_per_kernel", 2)
    kw.setdefault("scale", 0.25 if graph else 0.004)
    return prepare(make_trace(app, graph, **kw))


@pytest.fixture(scope="module")
def small_pair():
    return _small("pagerank", "arxiv"), _small("components", "arxiv")


def test_bucket_bound_is_pow4():
    assert [bucket_bound(n) for n in (1, 2, 4, 5, 16, 17, 4096, 4097)] == \
        [1, 4, 4, 16, 16, 64, 4096, 16384]
    with pytest.raises(ValueError):
        bucket_bound(0)


def test_workload_exactly_at_bucket_max(small_pair):
    """A trace whose num_lines is exactly its bucket bound gains no pad
    lines and still round-trips bit-exactly through the batch path."""
    tt, _ = small_pair
    bound = bucket_bound(tt.num_lines)
    exact = pad_trace(tt, num_lines=bound)
    assert exact.num_lines == bound == bucket_bound(exact.num_lines)
    [(idx, padded)] = bucket_traces([exact])
    assert idx == [0] and padded[0].num_lines == bound
    [br] = _batch([exact])
    seq = run_all(tt, HW)
    for m in seq:
        _assert_equal(seq[m], br[m], f"at-bound/{m}")


def test_singleton_bucket(small_pair):
    """A geometry with no bucket-mates forms a batch of one and matches the
    sequential path exactly."""
    small, other = small_pair
    big = _small("htap128", None)  # lands alone in a distant bucket
    buckets = bucket_traces([small, other, big])
    sizes = sorted(len(idx) for idx, _ in buckets)
    assert sizes == [1, 2]
    results = _batch([small, other, big], mechanisms=("cg", "lazypim"))
    for tt, br in zip((small, other, big), results):
        for m, r in br.items():
            _assert_equal(run_all(tt, HW, mechanisms=(m,))[m], r,
                          f"singleton/{tt.name}/{m}")


@pytest.mark.parametrize("pair", ["lines", "kernels"])
def test_stack_traces_still_rejects_raw_geometry_mismatch(pair, small_pair):
    """Bucketing routes mixed fleets around stack_traces; a *raw* mismatched
    stack must still fail loudly rather than silently mis-shape — whether
    the line counts differ or only the kernel counts."""
    if pair == "lines":
        a, b = small_pair[0], _small("htap128", None)
    else:
        a, b = (_small("pagerank", "arxiv", threads=4, num_kernels=k,
                       scale=0.4) for k in (2, 3))
    with pytest.raises(ValueError, match="geometry differs"):
        stack_traces([a, b])
    # ... while the batch engine handles the same list through bucketing.
    assert len(_batch([a, b], mechanisms=("nc",))) == 2


def test_pad_trace_rejects_shrinking(small_pair):
    tt, _ = small_pair
    with pytest.raises(ValueError, match="cannot shrink"):
        pad_trace(tt, num_lines=tt.num_lines - 1)
    with pytest.raises(ValueError, match="cannot shrink"):
        pad_trace(tt, num_windows=tt.num_windows - 1)


def test_padded_line_tables_match_native_prepare(small_pair):
    """pad_trace's extended per-line tables are the ones a native prepare at
    the padded size would produce (same H3 positions, same register ids) —
    padding is indistinguishable from never touching the extra lines."""
    tt, _ = small_pair
    bound = bucket_bound(tt.num_lines)
    padded = pad_trace(tt, num_lines=bound)
    want = hash_positions(tt.spec, jnp.arange(bound, dtype=jnp.uint32))
    np.testing.assert_array_equal(np.asarray(padded.line_pos),
                                  np.asarray(want.astype(jnp.int32)).T)
    np.testing.assert_array_equal(np.asarray(padded.line_reg),
                                  np.arange(bound) % P.CPUWS_REGS)


@pytest.mark.parametrize("lanes,num_lines,want", [
    (8, 1 << 20, 1),    # the large bucket: one lane per scan step
    (5, 1 << 18, 1),
    (11, 1 << 16, 1),   # cap 4, but 11 is prime: equal groups only
    (8, 1 << 16, 4),
    (6, 1 << 14, 6),    # every lane fits one step: a plain vmap
])
def test_lanes_per_step(lanes, num_lines, want):
    """The fleet differential above takes the grouped path (its 64 Ki- and
    256 Ki-line buckets run one lane per step) and the vmapped one (16 Ki)
    and holds both bit-exact with sequential runs."""
    assert lanes_per_step(lanes, num_lines) == want
