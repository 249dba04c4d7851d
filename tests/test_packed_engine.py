"""Differential tests: packed uint32-word engine vs the boolean seed path.

The packed primitives in ``repro.sim.prep`` and the packed simulators in
``repro.core.mechanisms`` / ``repro.core.coherence`` must be *bit-exact*
with the ``*_bool`` seed references (``repro.core._boolref``): same
bitmaps, same Bloom images, same conflict decisions, and identical
``SimResult`` accumulators — every field, not just ``time_ns``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import _boolref
from repro.core.coherence import LazyPIMConfig, simulate_lazypim
from repro.core.signatures import default_spec
from repro.sim import prep as P
from repro.sim.costmodel import HWParams
from repro.sim.engine import run_all, sweep_cache_sizes
from repro.sim.prep import prepare
from repro.sim.study import Study
from repro.sim.trace import make_graph_trace, make_htap_trace

HW = HWParams()


@pytest.fixture(scope="module")
def tt():
    return prepare(make_graph_trace("components", "arxiv", threads=16,
                                    num_kernels=3, windows_per_kernel=2,
                                    scale=0.4))


@pytest.fixture(scope="module")
def tt_htap():
    return prepare(make_htap_trace("htap128", threads=16, num_kernels=3,
                                   windows_per_kernel=2, scale=0.004))


def _rand_bitmap(tt, seed, p=0.02):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random(tt.num_lines) < p)


# ---------------------------------------------------------------------------
# Packed primitives vs boolean seed references
# ---------------------------------------------------------------------------


def test_pack_unpack_roundtrip(tt):
    bm = _rand_bitmap(tt, 0)
    words = P.pack_bitmap(bm)
    assert words.shape == (tt.num_line_words,)
    np.testing.assert_array_equal(np.asarray(P.unpack_bitmap(words, tt.num_lines)),
                                  np.asarray(bm))
    # pad bits beyond num_lines stay zero
    pad = tt.num_line_words * 32 - tt.num_lines
    if pad:
        tail = np.asarray(words)[-1] >> (32 - pad)
        assert tail == 0


def test_popcount_matches_sum(tt):
    for seed in range(3):
        bm = _rand_bitmap(tt, seed, p=0.1 * (seed + 1))
        assert int(P.popcount_words(P.pack_bitmap(bm))) == int(jnp.sum(bm))


def test_scatter_set_matches_bool(tt):
    for w in (0, tt.num_windows - 1):
        base = _rand_bitmap(tt, w)
        a = P.scatter_set_bool(base, tt.cpu_writes[w], tt.cpu_w_valid[w])
        b = P.scatter_set(P.pack_bitmap(base), tt.cpu_writes[w],
                          tt.cpu_w_valid[w], tt.num_lines)
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(P.unpack_bitmap(b, tt.num_lines)))


def test_scatter_set_duplicates_and_empty(tt):
    # duplicate ids in one scatter and an all-invalid scatter
    ids = jnp.asarray([5, 5, 5, 9, 9, 0], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 1], bool)
    packed = P.scatter_set(jnp.zeros((tt.num_line_words,), jnp.uint32),
                           ids, valid, tt.num_lines)
    got = np.flatnonzero(np.asarray(P.unpack_bitmap(packed, tt.num_lines)))
    np.testing.assert_array_equal(got, [0, 5, 9])
    none = P.scatter_set(jnp.zeros((tt.num_line_words,), jnp.uint32),
                         ids, jnp.zeros((6,), bool), tt.num_lines)
    assert int(P.popcount_words(none)) == 0
    # -1 padding sentinels with valid=None must be dropped, not wrapped into
    # the last word (negative scatter indices) — regression.
    neg = P.scatter_set(jnp.zeros((tt.num_line_words,), jnp.uint32),
                        jnp.asarray([-1, -3, 4], jnp.int32), None, tt.num_lines)
    got = np.flatnonzero(np.asarray(P.unpack_bitmap(neg, tt.num_lines)))
    np.testing.assert_array_equal(got, [4])


def test_gather_hits_matches_bool(tt):
    bm = _rand_bitmap(tt, 3, p=0.3)
    words = P.pack_bitmap(bm)
    for w in (0, 1):
        a = P.gather_hits_bool(bm, tt.cpu_reads[w], tt.cpu_r_valid[w])
        b = P.gather_hits(words, tt.cpu_reads[w], tt.cpu_r_valid[w])
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sig_bits_from_ids_matches_bool(tt):
    for w in range(3):
        img = P.sig_bits_from_ids_bool(tt, tt.pim_reads[w], tt.pim_r_valid[w])
        packed = P.sig_bits_from_ids(tt, tt.pim_reads[w], tt.pim_r_valid[w])
        np.testing.assert_array_equal(np.asarray(P.pack_bitmap(img)),
                                      np.asarray(packed))


def test_sig_and_bank_from_bitmap_match_bool(tt):
    bm = _rand_bitmap(tt, 7)
    words = P.pack_bitmap(bm)
    np.testing.assert_array_equal(
        np.asarray(P.pack_bitmap(P.sig_bits_from_bitmap_bool(tt, bm))),
        np.asarray(P.sig_bits_from_bitmap(tt, words)))
    bank_b = P.bank_bits_from_bitmap_bool(tt, bm)
    bank_p = P.bank_bits_from_bitmap(tt, words)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(P.pack_bitmap)(bank_b)), np.asarray(bank_p))


def test_conflict_and_members_match_bool(tt):
    pos_bm = P.line_pos_bit_major(tt)
    for seed in range(4):
        bm = _rand_bitmap(tt, seed, p=0.005 * (seed + 1))
        words = P.pack_bitmap(bm)
        img_b = P.sig_bits_from_ids_bool(tt, tt.pim_reads[seed],
                                         tt.pim_r_valid[seed])
        wimg_b = P.sig_bits_from_ids_bool(tt, tt.pim_writes[seed],
                                          tt.pim_w_valid[seed])
        img_p = P.pack_bitmap(img_b)
        c_bool = P.conflict_any_bool(tt, img_b, P.bank_bits_from_bitmap_bool(tt, bm))
        c_packed = P.conflict_any(tt, img_p, P.bank_bits_from_bitmap(tt, words))
        hits, whits = P.sig_hit_words(tt, pos_bm, img_p, P.pack_bitmap(wimg_b))
        assert hits.shape == whits.shape == (tt.num_segments, tt.num_line_words)
        c_fused = P.conflict_from_hit_words(words, hits)
        assert bool(c_bool) == bool(c_packed) == bool(c_fused)
        for img, h in ((img_b, hits), (wimg_b, whits)):
            np.testing.assert_array_equal(
                np.asarray(P.pack_bitmap(P.members_bool(tt, bm, img))),
                np.asarray(words & P.hit_member_words(h)))


def _lines_trace(num_lines):
    return P.dummy_trace(default_spec(), num_lines=num_lines, num_windows=1,
                         num_kernels=1, pim_read_slots=1, pim_write_slots=1,
                         cpu_read_slots=1, cpu_write_slots=1)


@pytest.mark.parametrize("case", ["reg0", "reg15", "pad"])
def test_conflict_word_fold(case):
    """The word-fold conflict and membership against the boolean bank.

    ``reg0``/``reg15``: lines of register r only, one at bit r of a word
    and one at bit r + 16 of the next; the image holds the first line's
    hash bits in the low half of the segments and the second's in the high
    half, so only folding bit r + 16 onto register r sees every segment.
    ``pad``: 1000 lines, so the last word has pad bits; the image holds the
    last line's hash bits, which the pad lines repeat, and the bitmap every
    line but the last — the pad lines' hits are set and must never count."""
    tt = _lines_trace(1000 if case == "pad" else 4096)
    n, m_segs = tt.num_lines, tt.num_segments
    pos = np.asarray(tt.line_pos)
    bm_sets = []
    if case == "pad":
        assert n % 32
        img_pos = pos[:, n - 1]
        bm_sets.append(np.arange(n - 1))
    else:
        r = 0 if case == "reg0" else 15
        lo, hi = r, 32 + 16 + r  # bit r of word 0, bit r + 16 of word 1
        half = m_segs // 2
        img_pos = np.concatenate([pos[:half, lo], pos[half:, hi]])
        bm_sets += [np.array([lo, hi]), np.array([lo]), np.array([hi])]
    img_b = jnp.asarray(np.isin(np.arange(tt.sig_bits), img_pos))
    img_p = P.pack_bitmap(img_b)
    # The complement rides along as a second image of the same lookup.
    hits, not_hits = P.sig_hit_words(tt, P.line_pos_bit_major(tt), img_p, ~img_p)
    got = []
    for lines in bm_sets:
        bm = jnp.asarray(np.isin(np.arange(n), lines))
        words = P.pack_bitmap(bm)
        want = bool(P.conflict_any_bool(
            tt, img_b, P.bank_bits_from_bitmap_bool(tt, bm)))
        assert bool(P.conflict_from_hit_words(words, hits)) == want
        got.append(want)
        for img, h in ((img_b, hits), (~img_b, not_hits)):
            np.testing.assert_array_equal(
                np.asarray(P.pack_bitmap(P.members_bool(tt, bm, img))),
                np.asarray(words & P.hit_member_words(h)))
    if case == "pad":
        tail = np.asarray(hits)[:, -1] >> np.uint32(n % 32)
        assert (tail != 0).all() and got == [False]
    else:
        assert got[0]  # both halves together cover every segment


def test_evict_to_cap_matches_bool(tt):
    present = _rand_bitmap(tt, 11, p=0.5)
    dirty = present & _rand_bitmap(tt, 12, p=0.6)
    for w, cap in ((3, 64), (9, 1 << 20)):  # over and under cap
        wdx = jnp.asarray(w)
        pb, db, wbb = P.evict_to_cap_bool(present, dirty, wdx, cap)
        pp, dp, wbp = P.evict_to_cap(P.pack_bitmap(present), P.pack_bitmap(dirty),
                                     wdx, cap, tt.num_lines)
        np.testing.assert_array_equal(np.asarray(P.pack_bitmap(pb)), np.asarray(pp))
        np.testing.assert_array_equal(np.asarray(P.pack_bitmap(db)), np.asarray(dp))
        assert float(wbb) == float(wbp)


def test_uniq_count_vectorized_matches_loop():
    rng = np.random.default_rng(0)
    rows = rng.integers(-1, 40, size=(64, 96)).astype(np.int32)
    rows[5] = -1  # fully-padded row
    other = rng.integers(-1, 40, size=(64, 64)).astype(np.int32)
    np.testing.assert_array_equal(P._uniq_count(rows), P._uniq_count_loop(rows))
    np.testing.assert_array_equal(P._uniq_union_count(rows, other),
                                  P._uniq_union_count_loop(rows, other))


def _pack_rows_widen_shift_sum(bits):
    """The seed formula of ``_pack_rows_np``: widen to uint32, shift each bit
    to its place, sum 32-wide rows (kept here as the reference)."""
    n = bits.shape[-1]
    pad = (-n) % 32
    if pad:
        bits = np.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    b = bits.reshape(*bits.shape[:-1], -1, 32).astype(np.uint32)
    return (b << np.arange(32, dtype=np.uint32)).sum(
        -1, dtype=np.uint64).astype(np.uint32)


def _check_pack_rows(bits):
    n = bits.shape[-1]
    words = P._pack_rows_np(bits)
    assert words.dtype == np.uint32
    assert words.flags.c_contiguous
    assert words.shape == (*bits.shape[:-1], P.packed_words(n))
    np.testing.assert_array_equal(words, _pack_rows_widen_shift_sum(bits))
    flat = jnp.asarray(bits).reshape(-1, n)
    by_jax = np.asarray(jax.vmap(P.pack_bitmap)(flat)).reshape(words.shape)
    np.testing.assert_array_equal(words, by_jax)
    # pad bits beyond n are zero
    unpacked = (words[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    assert not unpacked.reshape(*words.shape[:-1], -1)[..., n:].any()


@pytest.mark.parametrize("n", [1, 31, 32, 33, 1000, 16 * 32 + 16])
@pytest.mark.parametrize("rows", [1, 3, 24])
@pytest.mark.parametrize("density", [0.0, 0.01, 0.5, 1.0])
def test_pack_rows_np_matches_references(n, rows, density):
    rng = np.random.default_rng(n * 100 + rows)
    _check_pack_rows(rng.random((rows, n)) < density)


@pytest.mark.parametrize("layout", ["1d", "column_slice", "int01"])
def test_pack_rows_np_layouts(layout):
    rng = np.random.default_rng(7)
    bits = rng.random((3, 2 * 1000 + 1)) < 0.3
    if layout == "1d":
        bits = bits[0]
    elif layout == "column_slice":
        bits = bits[:, ::2]
        assert not bits.flags.c_contiguous
    else:
        bits = bits.astype(np.int32)
    _check_pack_rows(bits)


@pytest.mark.parametrize("backend", ["ref", "jax"])
def test_prepare_packs_pre_writes(backend):
    trace = make_htap_trace("htap128", threads=16, num_kernels=3,
                            windows_per_kernel=2, scale=0.004,
                            backend=backend)
    assert isinstance(trace.pre_writes,
                      np.ndarray if backend == "ref" else jax.Array)
    prepped = prepare(trace)
    words = np.asarray(prepped.pre_writes_words)
    assert words.shape == (3, P.packed_words(trace.num_lines))
    np.testing.assert_array_equal(
        words, np.asarray(jax.vmap(P.pack_bitmap)(prepped.pre_writes)))
    np.testing.assert_array_equal(
        words, _pack_rows_widen_shift_sum(np.asarray(trace.pre_writes)))


# ---------------------------------------------------------------------------
# Full-simulation differentials: every accumulator of every mechanism
# ---------------------------------------------------------------------------


def _assert_results_equal(a, b, label):
    da, db = dataclasses.asdict(a), dataclasses.asdict(b)
    for k in da:
        assert da[k] == db[k], f"{label}: field {k}: packed={da[k]} bool={db[k]}"


@pytest.mark.parametrize("fixture", ["tt", "tt_htap"])
def test_all_mechanisms_bit_exact(fixture, request):
    tt = request.getfixturevalue(fixture)
    packed = run_all(tt, HW)
    boolean = _boolref.run_all_bool(tt, HW)
    for m in packed:
        _assert_results_equal(packed[m], boolean[m], f"{tt.name}/{m}")


@pytest.mark.parametrize("fixture", ["tt", "tt_htap"])
def test_lazypim_full_commit_ablation_bit_exact(fixture, request):
    """The fig12 ablation (partial_commits=False) exercises the accumulate-
    across-windows dataflow; it must match the seed path too."""
    tt = request.getfixturevalue(fixture)
    cfg = LazyPIMConfig(partial_commits=False)
    _assert_results_equal(simulate_lazypim(tt, HW, cfg),
                          _boolref.simulate_lazypim_bool(tt, HW, cfg),
                          f"{tt.name}/lazypim-fullcommit")


def test_lazypim_no_dbi_bit_exact(tt):
    cfg = LazyPIMConfig(use_dbi=False)
    _assert_results_equal(simulate_lazypim(tt, HW, cfg),
                          _boolref.simulate_lazypim_bool(tt, HW, cfg),
                          "lazypim-nodbi")


# ---------------------------------------------------------------------------
# Sweep engine: batched == sequential, one compile per mechanism
# ---------------------------------------------------------------------------


def test_run_sweep_matches_sequential_loop():
    threads = (4, 8, 12, 16)
    tts = [prepare(make_graph_trace("pagerank", "arxiv", threads=t,
                                    num_kernels=3, windows_per_kernel=2,
                                    scale=0.4))
           for t in threads]
    hws = [HWParams(cpu_cores=t, pim_cores=t) for t in threads]
    study = Study(workloads=tts, hw=hws)
    (bucket,) = study.plan(devices=1).buckets
    before = sweep_cache_sizes()
    points = study.run(devices=1).points
    after = sweep_cache_sizes()
    # one compile per mechanism for the whole 4-point sweep (measured)
    assert bucket["lanes"] == len(threads)
    assert all(after[m] - before[m] <= 1 for m in after)
    for i in range(len(threads)):
        seq = run_all(tts[i], hws[i])
        for m, r in points[i].results.items():
            _assert_results_equal(r, seq[m], f"sweep[{i}]/{m}")
