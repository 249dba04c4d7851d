"""repro.capture subsystem tests.

* a **differential test**: the KV-capture recorder's PIM line stream and
  pre-write sets against a small *hand-computed* decode transcript (the
  request mix pinned via ``fixed_prompt_tokens``/``fixed_decode_tokens``
  and ``attn_reads_per_req=0``, so the stream is pure page/slot
  arithmetic);
* windower unit behavior (insert-cap splitting, CPU subsampling);
* geometry: layouts pad to ``prep.bucket_bound`` pow4 buckets and the
  recorder rejects ragged line counts;
* first-class-workload integration: ``make_trace`` routing + naming
  ValueErrors, ``all_workloads(captured=)``, serve admission, and
  bit-exact batched ``Study`` vs sequential ``run_all`` on captured traces;
* fixed-seed determinism per (model seed, request-mix seed).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.capture import CAPTURE_APPS, KVServeConfig, WindowRecorder
from repro.capture.kv_serve import (
    LINES_PER_PAGE,
    LINES_PER_TOKEN,
    capture_kv_serve,
    pt_line,
    token_lines,
)
from repro.capture.layout import LineLayout
from repro.capture.recorder import split_step, subsample_even
from repro.sim.costmodel import HWParams
from repro.sim.engine import run_all
from repro.sim.prep import bucket_bound, prepare
from repro.sim.study import Study
from repro.sim.trace import MAX_SIG_ADDRS, all_workloads, build_plan, make_trace

HW = HWParams()
TINY = dict(num_kernels=3, windows_per_kernel=2, scale=0.05)


@pytest.fixture(scope="module")
def tiny_traces():
    return {app: make_trace(app, seed=1, **TINY) for app in CAPTURE_APPS}


# ---------------------------------------------------------------------------
# Differential: hand-computed KV decode transcript
# ---------------------------------------------------------------------------


def test_kv_differential_hand_transcript():
    """Pin the request mix and replay the decode loop by hand.

    Config: 8 pages (page 0 = shared prefix), batch 2, every prompt
    exactly 2 tokens, decode long enough that nobody finishes.  Layout:
    ``pages`` at line 0 (8 × 128 lines), ``page_table`` at line 1024
    (one line holds all 8 entries), padded region = 4096 lines.

    Transcript: requests 0/1 get pages 1/2 with tokens 0..1 prefilled;
    each decode step appends one token per request (8 lines at
    ``page·128 + slot·8``) and reads the page-table line (1024) plus the
    previous token's 8 lines.  Kernels are 2 steps; the inter-kernel
    host phase re-writes the live tail page-table entries (line 1024).
    """
    cfg = KVServeConfig(num_pages=8, shared_pages=1, batch=2,
                        fixed_prompt_tokens=2, fixed_decode_tokens=100,
                        attn_reads_per_req=0)
    tr = capture_kv_serve(threads=16, seed=0, num_kernels=2,
                          windows_per_kernel=2, cfg=cfg)

    assert tr.num_lines == 4096
    assert tr.num_windows == 4 and tr.num_kernels == 2

    def tok(page, slot):
        return list(range(page * 128 + slot * 8, page * 128 + slot * 8 + 8))

    PT = 1024  # the single page-table line
    # step s (s = 0..3) appends slot 2+s of page 1 (req 0) then page 2
    # (req 1); reads = PT + previous slot's lines, per request.
    for s in range(4):
        expect_w = tok(1, 2 + s) + tok(2, 2 + s)
        expect_r = [PT] + tok(1, 1 + s) + [PT] + tok(2, 1 + s)
        row_w = tr.pim_writes[s]
        row_r = tr.pim_reads[s]
        assert list(row_w[row_w >= 0]) == expect_w, f"step {s} writes"
        assert list(row_r[row_r >= 0]) == expect_r, f"step {s} reads"
        # CPU writes happen only on page allocation — none in 4 steps
        # (both requests stay inside their prompt page until slot 15)
        assert np.all(tr.cpu_writes[s] == -1)
        # CPU reads: one shared-prefix line per request (random line
        # *within* page 0 — bounded, not pinned)
        row_cr = tr.cpu_reads[s]
        assert np.all((row_cr[row_cr >= 0] >= 0)
                      & (row_cr[row_cr >= 0] < 128))

    # kernel 0 pre-writes: shared page 0 (lines 0..127), both prompts
    # (pages 1..2, tokens 0..1), and the page-table line
    pre0 = set(np.flatnonzero(tr.pre_writes[0]))
    assert pre0 == (set(range(128)) | set(tok(1, 0)) | set(tok(1, 1))
                    | set(tok(2, 0)) | set(tok(2, 1)) | {PT})
    # kernel 1 pre-writes: just the scheduler's page-table checkpoint
    assert set(np.flatnonzero(tr.pre_writes[1])) == {PT}

    # run the same transcript past the page boundary: at step 14 both
    # requests write token 16 = slot 0 of a fresh page (3 for req 0, 4
    # for req 1, lowest-free-first), and the *scheduler* writes the new
    # page-table entries — the allocation-race CPU writes
    tr2 = capture_kv_serve(threads=16, seed=0, num_kernels=8,
                           windows_per_kernel=2, cfg=cfg)
    row_w = tr2.pim_writes[14]
    row_r = tr2.pim_reads[14]
    row_cw = tr2.cpu_writes[14]
    assert list(row_w[row_w >= 0]) == tok(3, 0) + tok(4, 0)
    assert list(row_r[row_r >= 0]) == [PT] + tok(1, 15) + [PT] + tok(2, 15)
    assert list(row_cw[row_cw >= 0]) == [PT, PT]

    # the pure helpers agree with the hand arithmetic
    layout = cfg.layout()
    assert list(token_lines(layout, 2, 3)) == tok(2, 3)
    assert pt_line(layout, 7) == PT
    assert LINES_PER_PAGE == 128 and LINES_PER_TOKEN == 8


# ---------------------------------------------------------------------------
# Windower unit behavior
# ---------------------------------------------------------------------------


def test_split_step_insert_cap():
    ids = np.arange(2 * MAX_SIG_ADDRS + 10)
    subs = split_step(ids, ids[:5], None, None)
    assert len(subs) == 3
    np.testing.assert_array_equal(np.concatenate([s[0] for s in subs]), ids)
    for pr, pw, cr, cw in subs:
        assert len(pr) <= MAX_SIG_ADDRS and len(pw) <= MAX_SIG_ADDRS
        assert len(cr) == 0 and len(cw) == 0
    # a single short step stays one window
    assert len(split_step(ids[:10], ids[:10], ids[:3], None)) == 1


def test_subsample_even():
    ids = np.arange(1000)
    out = subsample_even(ids, 64)
    assert len(out) == 64 and out[0] == 0
    assert np.all(np.diff(out) > 0)  # order-preserving spread
    np.testing.assert_array_equal(subsample_even(ids[:10], 64), ids[:10])


def test_recorder_rejects_bad_geometry_and_empty_phases():
    with pytest.raises(AssertionError, match="bucket_bound"):
        WindowRecorder("x", 1000, 16, 6.0)  # not a pow4 bucket
    rec = WindowRecorder("x", 1024, 16, 6.0)
    with pytest.raises(AssertionError, match="empty"):
        rec.begin_kernel([])
    with pytest.raises(AssertionError, match="before begin_kernel"):
        rec.step(pim_reads=[1])
    rec.begin_kernel([5])
    with pytest.raises(AssertionError, match="out of"):
        rec.step(pim_reads=[1024])


def test_layout_pads_to_pow4_bucket():
    lay = LineLayout.build([("a", 100), ("b", 30)])
    assert lay.natural_lines == 130
    assert lay.num_lines == bucket_bound(130) == 256
    assert lay.region("b").base == 100
    with pytest.raises(ValueError, match="out of"):
        lay.region("a").line(100)
    with pytest.raises(KeyError):
        lay.region("c")


# ---------------------------------------------------------------------------
# Captured traces as first-class workloads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("app", CAPTURE_APPS)
def test_capture_trace_valid_and_bucketed(tiny_traces, app):
    tr = tiny_traces[app]
    assert tr.name == app
    assert tr.num_lines == bucket_bound(tr.num_lines), \
        "capture leaked a ragged geometry"
    prepare(tr)  # stages without raising
    for name in ("pim_reads", "pim_writes", "cpu_reads", "cpu_writes"):
        ids = np.asarray(getattr(tr, name))
        assert ids.dtype == np.int32
        assert np.all((ids == -1) | ((ids >= 0) & (ids < tr.num_lines)))
    pre = np.asarray(tr.pre_writes)
    assert pre.dtype == bool and pre.any(axis=1).all()


@pytest.mark.parametrize("app", CAPTURE_APPS)
def test_capture_determinism(tiny_traces, app):
    """Same (model seed, request-mix seed) => bit-identical WindowTrace;
    a different seed actually changes the stream."""
    tr = tiny_traces[app]
    again = make_trace(app, seed=1, **TINY)
    other = make_trace(app, seed=2, **TINY)
    diff = False
    for f in dataclasses.fields(tr):
        a, b = getattr(tr, f.name), getattr(again, f.name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f.name)
        o = getattr(other, f.name)
        diff |= not np.array_equal(np.asarray(a), np.asarray(o))
    assert diff, "seed had no effect on the captured stream"


def test_capture_backend_uniform(tiny_traces):
    """Both make_trace backends run the single recorder implementation."""
    tr = tiny_traces["capture/kv_serve"]
    ref = make_trace("capture/kv_serve", seed=1, backend="ref", **TINY)
    np.testing.assert_array_equal(tr.pim_writes, ref.pim_writes)
    with pytest.raises(ValueError, match="backend"):
        make_trace("capture/kv_serve", seed=1, backend="bogus", **TINY)


def test_naming_valueerrors():
    with pytest.raises(ValueError, match="unknown capture spec"):
        make_trace("capture/bogus")
    with pytest.raises(ValueError, match="graph_name must be None"):
        make_trace("capture/kv_serve", "enron")
    with pytest.raises(ValueError, match="recorded from live"):
        build_plan("capture/kv_serve")


def test_all_workloads_captured_flag():
    base = all_workloads()
    ext = all_workloads(extended=True)
    cap = all_workloads(extended=True, captured=True)
    assert [a for a, _ in cap[len(ext):]] == list(CAPTURE_APPS)
    assert all_workloads(captured=True)[len(base):] == \
        [(a, None) for a in CAPTURE_APPS]
    assert not any(a.startswith("capture/") for a, _ in ext), \
        "captured families must stay opt-in"


def test_serve_admission():
    from repro.serve.request import build_study

    study = build_study({"workloads": list(CAPTURE_APPS),
                         "mechanisms": ["cpu", "lazypim"], "threads": 16})
    assert len(study.workloads) == 3
    with pytest.raises(ValueError, match="unknown workload"):
        build_study({"workloads": ["capture/bogus"]})


def test_run_batch_bit_exact(tiny_traces):
    """Captured traces through the geometry-bucketed batch engine ==
    the sequential reference engine, on every SimResult field."""
    tts = [prepare(tr) for tr in tiny_traces.values()]
    batched = Study(workloads=tts, hw=HW).run().points
    for tt, p in zip(tts, batched):
        br = p.results
        for m, r in br.items():
            seq = run_all(tt, HW, mechanisms=(m,))[m]
            da, db = dataclasses.asdict(seq), dataclasses.asdict(r)
            for k in da:
                assert da[k] == db[k], f"{tt.name}/{m}: {k}"


def test_roofline_intensity(tiny_traces):
    from repro.roofline.analysis import trace_intensity

    prof = trace_intensity(tiny_traces["capture/kv_serve"])
    assert prof["pim_bytes"] > 0 and prof["cpu_bytes"] > 0
    assert prof["lines_touched"] > 0
    assert prof["bytes_per_line_touch"] >= 64.0
    assert prof["pim_instr_per_byte"] > 0
