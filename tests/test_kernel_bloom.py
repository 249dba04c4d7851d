"""Pallas Bloom kernels vs pure-jnp oracle: shape/dtype sweeps (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import signatures as S
from repro.core.signatures import SignatureSpec
from repro.kernels.bloom import bloom as K
from repro.kernels.bloom import ref as R
from repro.kernels.bloom import ops

SPECS = {
    "paper_2k_m4": SignatureSpec(sig_bits=2048, num_segments=4),
    "small_1k_m2": SignatureSpec(sig_bits=1024, num_segments=2),
    "big_8k_m4": SignatureSpec(sig_bits=8192, num_segments=4),
}


def _addrs(n, seed=0, dtype=np.uint32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**31 - 1, size=(n,)).astype(dtype))


@pytest.mark.parametrize("spec_name", list(SPECS))
@pytest.mark.parametrize("n", [1, 7, 64, 300])
@pytest.mark.parametrize("dtype", [np.uint32, np.int32])
def test_insert_matches_ref(spec_name, n, dtype):
    spec = SPECS[spec_name]
    addrs = _addrs(n, seed=n, dtype=dtype)
    sig0 = S.empty_signature(spec)
    got = K.bloom_insert_pallas(spec, sig0, addrs, interpret=True, block_n=64)
    want = R.bloom_insert_ref(spec, sig0, addrs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("spec_name", list(SPECS))
def test_insert_with_mask_matches_ref(spec_name):
    spec = SPECS[spec_name]
    addrs = _addrs(90, seed=5)
    mask = jnp.asarray(np.random.default_rng(1).integers(0, 2, size=(90,)).astype(bool))
    sig0 = S.empty_signature(spec)
    got = K.bloom_insert_pallas(spec, sig0, addrs, mask, interpret=True, block_n=32)
    want = R.bloom_insert_ref(spec, sig0, addrs, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_insert_accumulates_into_existing_signature():
    spec = SPECS["paper_2k_m4"]
    a1, a2 = _addrs(40, 1), _addrs(40, 2)
    sig = K.bloom_insert_pallas(spec, S.empty_signature(spec), a1, interpret=True)
    sig = K.bloom_insert_pallas(spec, sig, a2, interpret=True)
    want = R.bloom_insert_ref(spec, R.bloom_insert_ref(spec, S.empty_signature(spec), a1), a2)
    np.testing.assert_array_equal(np.asarray(sig), np.asarray(want))


@pytest.mark.parametrize("spec_name", list(SPECS))
@pytest.mark.parametrize("n", [1, 33, 128])
def test_query_matches_ref(spec_name, n):
    spec = SPECS[spec_name]
    inserted = _addrs(120, seed=3)
    sig = R.bloom_insert_ref(spec, S.empty_signature(spec), inserted)
    # probe a mix of present and absent addresses
    probes = jnp.concatenate([inserted[: n // 2 + 1], _addrs(n, seed=99)])[:n]
    got = K.bloom_query_pallas(spec, sig, probes, interpret=True, block_n=32)
    want = R.bloom_query_ref(spec, sig, probes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("spec_name", list(SPECS))
@pytest.mark.parametrize("batch", [1, 5, 16, 37])
def test_intersect_matches_ref(spec_name, batch):
    spec = SPECS[spec_name]
    rng = np.random.default_rng(batch)
    sigs_a, sigs_b = [], []
    for i in range(batch):
        na, nb = rng.integers(0, 200), rng.integers(0, 200)
        a = R.bloom_insert_ref(spec, S.empty_signature(spec), _addrs(max(na, 1), i) if na else _addrs(1, i))
        if na == 0:
            a = S.empty_signature(spec)
        b = R.bloom_insert_ref(spec, S.empty_signature(spec), _addrs(max(nb, 1), i + 1000) if nb else _addrs(1, i))
        if nb == 0:
            b = S.empty_signature(spec)
        sigs_a.append(a)
        sigs_b.append(b)
    A, B = jnp.stack(sigs_a), jnp.stack(sigs_b)
    got = K.bloom_intersect_pallas(spec, A, B, interpret=True, block_b=4)
    want = R.bloom_intersect_ref(spec, A, B)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_wrappers_dispatch_to_ref_on_cpu():
    spec = SPECS["paper_2k_m4"]
    addrs = _addrs(100, 0)
    sig = ops.bloom_insert(spec, S.empty_signature(spec), addrs)
    assert bool(ops.bloom_query(spec, sig, addrs).all())
    flags = ops.bloom_intersect(spec, sig[None], sig[None])
    assert bool(flags[0])


def test_ops_pallas_path_cpu_interpret():
    spec = SPECS["paper_2k_m4"]
    addrs = _addrs(64, 9)
    sig = ops.bloom_insert(spec, S.empty_signature(spec), addrs, use_pallas=True,
                           interpret=True)
    want = ops.bloom_insert(spec, S.empty_signature(spec), addrs, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(sig), np.asarray(want))
