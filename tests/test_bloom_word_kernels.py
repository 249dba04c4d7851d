"""Word-level Pallas kernels vs reference, across geometries, plus H3
byte-sliced vs xor-fold bit-exactness.

The acceptance contract of the perf pass: the optimized hot path must be
*bit-identical* to the seed implementations — same packed signatures, same
membership/conflict bits — for the same ``SignatureSpec`` seed.  Sweeps
sig_bits in {512, 2048, 4096} x M in {2, 4, 8} (every valid combination:
sig_bits must be a multiple of 32*M).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import signatures as S
from repro.core.signatures import SignatureSpec
from repro.kernels.bloom import bloom as K
from repro.kernels.bloom import ops
from repro.kernels.bloom import ref as R

GEOMETRIES = [
    (sig_bits, m)
    for sig_bits in (512, 2048, 4096)
    for m in (2, 4, 8)
    if sig_bits % (32 * m) == 0
]


def _spec(sig_bits, m):
    return SignatureSpec(sig_bits=sig_bits, num_segments=m)


def _addrs(n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, size=(n,), dtype=np.uint64).astype(np.uint32))


# ---------------------------------------------------------------------------
# H3 bit-exactness: byte-sliced tables == per-bit xor-fold
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sig_bits,m", GEOMETRIES)
def test_bytesliced_h3_equals_xorfold(sig_bits, m):
    spec = _spec(sig_bits, m)
    addrs = _addrs(2048, seed=sig_bits + m)
    np.testing.assert_array_equal(
        np.asarray(S.hash_positions(spec, addrs)),
        np.asarray(S.hash_positions_xorfold(spec, addrs)),
    )


def test_nonpow2_segments_rejected():
    # seg_bits = 384 is not a power of two: H3's XOR is not closed under a
    # non-pow2 bound, so such geometries hash past the segment and would
    # produce membership false negatives (latent seed bug) — now rejected.
    with pytest.raises(ValueError, match="power"):
        SignatureSpec(sig_bits=1536, num_segments=4)


def test_h3_tables_derive_from_matrix():
    """Table construction invariant: XOR of per-byte entries reproduces the
    xor-fold of the underlying H3 matrix for every address byte pattern."""
    spec = S.default_spec()
    tabs = spec.h3_tables  # (S, 256, M), segment-local
    q = spec.h3_matrix  # (M, addr_bits)
    rng = np.random.default_rng(7)
    for a in rng.integers(0, 2**32, size=(64,), dtype=np.uint64).astype(np.uint32):
        want = np.zeros((spec.num_segments,), np.uint32)
        for j in range(spec.addr_bits):
            if (int(a) >> j) & 1:
                want ^= q[:, j]
        got = np.zeros((spec.num_segments,), np.uint32)
        for k in range(spec.num_byte_slices):
            got ^= tabs[k, (int(a) >> (8 * k)) & 0xFF]
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Word-level kernels vs pure-jnp reference across geometries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sig_bits,m", GEOMETRIES)
def test_word_insert_matches_ref(sig_bits, m):
    spec = _spec(sig_bits, m)
    addrs = _addrs(200, seed=m)
    sig0 = S.empty_signature(spec)
    got = K.bloom_insert_pallas(spec, sig0, addrs, interpret=True, block_n=64)
    want = R.bloom_insert_ref(spec, sig0, addrs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sig_bits,m", GEOMETRIES)
def test_word_query_matches_ref(sig_bits, m):
    spec = _spec(sig_bits, m)
    inserted = _addrs(150, seed=3)
    sig = R.bloom_insert_ref(spec, S.empty_signature(spec), inserted)
    probes = jnp.concatenate([inserted[:40], _addrs(88, seed=4)])
    got = K.bloom_query_pallas(spec, sig, probes, interpret=True, block_n=32)
    want = R.bloom_query_ref(spec, sig, probes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sig_bits,m", [(512, 2), (2048, 4), (4096, 8)])
@pytest.mark.parametrize("num_groups", [2, 4, 8])
def test_conflict_kernel_matches_ref(sig_bits, m, num_groups):
    spec = _spec(sig_bits, m)
    rng = np.random.default_rng(num_groups)
    sigs = jnp.stack([
        R.bloom_insert_ref(
            spec, S.empty_signature(spec), _addrs(100, seed=g)
        )
        for g in range(num_groups)
    ])
    probes = jnp.concatenate([_addrs(100, seed=0)[:50], _addrs(78, seed=1234)])
    got = K.bloom_detect_conflicts_pallas(spec, sigs, probes, interpret=True, block_n=64)
    want = R.bloom_detect_conflicts_ref(spec, sigs, probes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Word-level kernels vs the SEED one-hot kernels (same spec seed -> identical
# packed signatures and identical membership bits)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sig_bits,m", [(512, 4), (2048, 4), (4096, 8)])
def test_word_kernels_bitexact_with_seed_onehot(sig_bits, m):
    spec = _spec(sig_bits, m)
    addrs = _addrs(300, seed=sig_bits)
    mask = jnp.asarray(
        np.random.default_rng(1).integers(0, 2, size=(300,)).astype(bool)
    )
    sig0 = S.empty_signature(spec)
    new_sig = K.bloom_insert_pallas(spec, sig0, addrs, mask, interpret=True, block_n=64)
    old_sig = K.bloom_insert_pallas_onehot(
        spec, sig0, addrs, mask, interpret=True, block_n=64
    )
    np.testing.assert_array_equal(np.asarray(new_sig), np.asarray(old_sig))
    probes = jnp.concatenate([addrs[:64], _addrs(64, seed=5)])
    np.testing.assert_array_equal(
        np.asarray(K.bloom_query_pallas(spec, new_sig, probes, interpret=True)),
        np.asarray(
            K.bloom_query_pallas_onehot(spec, old_sig, probes, interpret=True)
        ),
    )


def test_ops_detect_conflicts_wrapper():
    spec = S.default_spec()
    sigs = jnp.stack([
        R.bloom_insert_ref(spec, S.empty_signature(spec), _addrs(80, seed=g))
        for g in range(4)
    ])
    probes = _addrs(128, seed=0)
    ref_counts = ops.bloom_detect_conflicts(spec, sigs, probes, use_pallas=False)
    knl_counts = ops.bloom_detect_conflicts(spec, sigs, probes, use_pallas=True,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_counts), np.asarray(knl_counts))
    # every group's own addresses must be counted (no false negatives)
    own = ops.bloom_detect_conflicts(spec, sigs, _addrs(80, seed=0))
    assert int(jnp.min(own)) >= 1
