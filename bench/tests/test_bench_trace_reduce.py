"""The trace reduction on hand-made intervals and on a small trace recorded
on a TPU v5e (``data/tiny.xplane.pb``: two spans ``bench:dispatch:lazypim``
and ``bench:dispatch:cpu`` around a jitted call each, a ``bench:prep``
span of host work between them, twice, inside ``bench:window``)."""

import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as TR  # noqa: E402

TINY = BENCH / "tests" / "data" / "tiny.xplane.pb"


def test_union_merges_overlapping_and_touching_intervals():
    s, e = TR.union([5, 0, 2, 20, 9], [8, 3, 4, 25, 10])
    assert s.tolist() == [0, 5, 9, 20] and e.tolist() == [4, 8, 10, 25]
    assert TR.total((s, e)) == 4 + 3 + 1 + 5


def test_clip_and_overlap_count_only_the_covered_part():
    merged = TR.union([0, 10, 20], [5, 15, 30])
    assert TR.total(TR.clip(merged, 3, 25)) == 2 + 5 + 5
    # windows [4, 12) and [11, 22): union [4, 22) holds 1 + 5 + 2 busy ns
    assert TR.overlap(merged, [(4, 12, "a"), (11, 22, "b")]) == 8
    assert TR.overlap(merged, []) == 0
    assert TR.busy_before(merged, [0, 5, 12, 100]).tolist() == [0, 5, 7, 20]


def test_idle_gaps_cover_the_window_with_the_busy_time():
    tr = TR.Trace(ops={"/device:TPU:0": TR.Ops(
        np.asarray([10, 40], np.int64), np.asarray([20, 45], np.int64),
        np.asarray([0, 1], np.int32), ["%a", "%b"])},
        spans=[(0, 100, "bench:window"), (25, 38, "bench:prep")])
    gaps = TR.idle_gaps(tr, 0, 100, k=10)
    assert sum(g[1] for g in gaps) * 1e9 + 15 == pytest.approx(100)
    assert gaps[0] == ["window", 55e-9] and ["prep", 20e-9] in gaps


@pytest.fixture(scope="module")
def tiny():
    tr = TR.load(str(TINY))
    return tr, TR.spans_named(tr, "bench:window")[0]


def test_recorded_trace_has_one_tpu_and_the_benchmark_spans(tiny):
    tr, _ = tiny
    assert list(tr.ops) == ["/device:TPU:0"]
    assert len(tr.ops["/device:TPU:0"].start) == 8
    names = [s[2] for s in tr.spans]
    assert names.count("bench:dispatch:lazypim") == 2
    assert names.count("bench:prep") == 2 and names.count("bench:window") == 1


def test_recorded_trace_busy_and_gaps_add_up_to_the_window(tiny):
    tr, (lo, hi, _) = tiny
    busy = TR.busy_per_device(tr, lo, hi)["/device:TPU:0"]
    assert busy == 8647
    gaps = TR.idle_gaps(tr, lo, hi, k=100)
    assert round(sum(g[1] for g in gaps) * 1e9) + busy == hi - lo
    assert sum(v for _, v in TR.top_ops(tr, lo, hi)) * 1e9 == pytest.approx(busy)


def test_alignment_moves_device_work_into_the_dispatch_spans(tiny):
    tr, (lo, hi, _) = tiny
    d = "/device:TPU:0"
    disp = [s for s in tr.spans if s[2].startswith("bench:dispatch:")]
    before = TR.overlap(TR.device_busy(tr, d, lo, hi), disp)
    off = TR.align(tr, scan_module="jit__lambda(")
    assert -2_000_000 < off[d] < -500_000  # the device clock runs early
    after = TR.overlap(TR.device_busy(tr, d, lo, hi), disp)
    assert before == 0 and after > 0.5 * TR.busy_per_device(tr, lo, hi)[d]
