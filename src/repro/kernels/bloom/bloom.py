"""Pallas TPU kernels for Bloom-signature insert / query / conflict detect.

The paper's hardware inserts one address per memory access into a 2 Kbit
register file next to the PIM L1.  On TPU we batch: a block of addresses is
H3-hashed on the VPU, decomposed into (word, bit) coordinates of the packed
signature, and scattered/gathered at *word* granularity.

Design notes (TPU-native, not a port):

* **Layout for the TPU kernel compiler.**  An address block is an
  ``(BLOCK_N, 1)`` int32 column (addresses down the sublanes), the packed
  signature a ``(1, num_words)`` int32 row (words across the lanes); uint32
  data is bitcast to int32 at the kernel boundary.  Every in-kernel value
  is 2-D, every reduction an int32 sum/max along one axis, and no value is
  reshaped — the forms the compiler lowers (``tests/test_tpu_compile.py``
  compiles each kernel for a described v5e chip).
* **H3 in-kernel as a constant xor-fold.**  The hash matrix is a static
  function of the spec, so each segment's hash is ``addr_bits`` selects +
  XORs against compile-time constants: no table operand and no gather,
  which the kernel compiler cannot lower.  Bit-exact with the byte-sliced
  XLA path (:func:`repro.core.signatures.hash_positions`).
* **Word/bit decomposition.**  A global bit position ``pos`` splits into
  ``word = pos >> 5`` (one of ``num_words`` packed words, 64 for the paper
  geometry) and ``bit = 1 << (pos & 31)``.  Insert compares ``word``
  against a ``num_words``-wide lane iota — 32x less compare work than the
  seed kernel's one-hot expand against the full ``sig_bits``-wide iota —
  and ORs the masked bits down the sublanes to one 8-row tile, which the
  wrapper folds.  Query selects the addressed word with the same compare
  (a sum that is exact: one hit per row) and tests the bit.  The seed
  one-hot kernels are kept as ``*_onehot`` for differential tests and the
  before/after microbench (``benchmarks/bench_signatures.py``).
* The 2 Kbit signature is tiny; the interesting tiling axis is the *address
  batch*.  ``BlockSpec`` tiles the address stream into VMEM and revisits
  the same whole-signature output block every grid step — the canonical
  Pallas accumulation pattern (TPU grids execute sequentially, so
  read-modify-write on the output ref is safe).
* ``bloom_detect_conflicts_pallas`` fuses the whole LazySync hot loop —
  hash -> membership across all G group signatures -> per-address hit-group
  count — into one kernel, so conflict detection reads only G*num_words
  packed words instead of G unpacked 2048-bit images.

All kernels are validated in ``interpret=True`` mode against ``ref.py``
(pure jnp) in ``tests/test_kernel_bloom.py`` and
``tests/test_bloom_word_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.signatures import SignatureSpec

DEFAULT_BLOCK_N = 256
_TILE_ROWS = 8  # sublanes per vreg tile


# ---------------------------------------------------------------------------
# In-kernel H3 hashing
# ---------------------------------------------------------------------------


def _h3_columns(addrs, spec: SignatureSpec) -> list:
    """H3 global bit positions of an (R, 1) int32 address column: one (R, 1)
    int32 column per segment.  Per-bit xor-fold with the hash matrix as
    compile-time constants and the segment offset added."""
    q = spec.h3_matrix
    bits = [((addrs >> j) & 1) != 0 for j in range(spec.addr_bits)]
    cols = []
    for m in range(spec.num_segments):
        h = jnp.zeros_like(addrs)
        for j, b in enumerate(bits):
            h = h ^ jnp.where(b, np.int32(q[m, j]), np.int32(0))
        cols.append(h + np.int32(m * spec.seg_bits))
    return cols


def _h3_hash_block_xorfold(addrs, q, spec: SignatureSpec):
    """Seed H3: unrolled xor-fold over the address bits (kept for the legacy
    one-hot kernels)."""
    addrs = addrs.astype(jnp.uint32)
    h = jnp.zeros((addrs.shape[0], spec.num_segments), dtype=jnp.uint32)
    for j in range(spec.addr_bits):
        bit = ((addrs >> np.uint32(j)) & np.uint32(1)).astype(bool)
        h = h ^ jnp.where(bit[:, None], q[None, :, j], np.uint32(0))
    seg_off = (
        jnp.arange(spec.num_segments, dtype=jnp.uint32) * np.uint32(spec.seg_bits)
    )
    return (h + seg_off[None, :]).astype(jnp.int32)


def _word_bit(pos):
    """Split int32 global positions into packed-word index and the int32
    bit pattern of its 32-bit lane mask."""
    return pos >> 5, jnp.left_shift(np.int32(1), pos & 31)


def _word_lanes(rows: int, spec: SignatureSpec):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, spec.num_words), 1)


def _looked_up(word, lanes, sig_row):
    """The packed word each row addresses: (R, 1) from a (1, W) row."""
    return jnp.sum(jnp.where(word == lanes, sig_row, 0), axis=1,
                   keepdims=True)


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _address_column(addrs: jax.Array, block_n: int) -> tuple[jax.Array, int]:
    """(N,) integer addresses -> ((N_pad, 1) int32 column, N)."""
    if block_n % _TILE_ROWS:
        raise ValueError(f"block_n must be a multiple of {_TILE_ROWS}, got "
                         f"{block_n}")
    col = _i32(addrs.reshape(-1).astype(jnp.uint32))
    n = col.shape[0]
    return jnp.pad(col, (0, (-n) % block_n))[:, None], n


def _column_spec(block_n: int):
    return pl.BlockSpec((block_n, 1), lambda i: (i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


# ---------------------------------------------------------------------------
# Word-level insert
# ---------------------------------------------------------------------------


def _or_to_tile(x):
    """OR-fold the rows of an (R, W) int32 array (R a multiple of 8) down to
    one (8, W) tile, with tile-aligned contiguous slices only."""
    rows = x.shape[0]
    while rows > _TILE_ROWS and rows % (2 * _TILE_ROWS) == 0:
        rows //= 2
        x = x[:rows] | x[rows:]
    out = x[:_TILE_ROWS]
    for r in range(_TILE_ROWS, rows, _TILE_ROWS):
        out = out | x[r:r + _TILE_ROWS]
    return out


def _insert_kernel(addr_ref, mask_ref, out_ref, *, spec: SignatureSpec):
    step = pl.program_id(0)
    addrs = addr_ref[...]
    live = mask_ref[...] > 0
    lanes = _word_lanes(addrs.shape[0], spec)
    contrib = None
    for pos in _h3_columns(addrs, spec):
        word, bit = _word_bit(pos)
        c = jnp.where((word == lanes) & live, bit, 0)  # (BLK, W)
        contrib = c if contrib is None else contrib | c
    tile = _or_to_tile(contrib)
    prev = jnp.where(step == 0, jnp.zeros_like(tile), out_ref[...])
    out_ref[...] = prev | tile


def bloom_insert_pallas(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    mask: jax.Array | None = None,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Insert a batch of addresses into a packed signature via Pallas.

    ``sig``: (num_words,) uint32; ``addrs``: (N,) integer; ``mask`` optional
    (N,) bool.  Returns the updated signature.
    """
    col, n = _address_column(addrs, block_n)
    live = (jnp.ones((n,), jnp.int32) if mask is None
            else mask.reshape(-1).astype(jnp.int32))
    live = jnp.pad(live, (0, col.shape[0] - n))[:, None]
    tile_shape = (_TILE_ROWS, spec.num_words)
    tile = pl.pallas_call(
        functools.partial(_insert_kernel, spec=spec),
        grid=(col.shape[0] // block_n,),
        in_specs=[_column_spec(block_n), _column_spec(block_n)],
        out_specs=_whole(tile_shape),
        out_shape=jax.ShapeDtypeStruct(tile_shape, jnp.int32),
        interpret=interpret,
    )(col, live)
    delta = jax.lax.reduce(tile, np.int32(0), jax.lax.bitwise_or, (0,))
    return sig | _u32(delta)


# ---------------------------------------------------------------------------
# Word-level query
# ---------------------------------------------------------------------------


def _query_kernel(addr_ref, sig_ref, out_ref, *, spec: SignatureSpec):
    addrs = addr_ref[...]
    sig = sig_ref[...]  # (1, W)
    lanes = _word_lanes(addrs.shape[0], spec)
    member = None
    for pos in _h3_columns(addrs, spec):
        word, bit = _word_bit(pos)
        hit = (_looked_up(word, lanes, sig) & bit) != 0
        member = hit if member is None else member & hit
    out_ref[...] = member.astype(jnp.int32)


def bloom_query_pallas(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Membership of ``addrs`` (N,) in ``sig`` -> (N,) bool via Pallas."""
    col, n = _address_column(addrs, block_n)
    row = _i32(sig)[None, :]
    out = pl.pallas_call(
        functools.partial(_query_kernel, spec=spec),
        grid=(col.shape[0] // block_n,),
        in_specs=[_column_spec(block_n), _whole(row.shape)],
        out_specs=_column_spec(block_n),
        out_shape=jax.ShapeDtypeStruct(col.shape, jnp.int32),
        interpret=interpret,
    )(col, row)
    return out[:n, 0].astype(bool)


# ---------------------------------------------------------------------------
# Fused conflict detection (LazySync hot loop)
# ---------------------------------------------------------------------------


def _conflict_kernel(addr_ref, sigs_ref, out_ref, *, spec: SignatureSpec):
    addrs = addr_ref[...]
    groups = sigs_ref.shape[0]
    lanes = _word_lanes(addrs.shape[0], spec)
    member = [None] * groups
    for pos in _h3_columns(addrs, spec):
        word, bit = _word_bit(pos)
        for g in range(groups):
            hit = (_looked_up(word, lanes, sigs_ref[g:g + 1, :]) & bit) != 0
            member[g] = hit if member[g] is None else member[g] & hit
    out_ref[...] = sum(m.astype(jnp.int32) for m in member)


def bloom_detect_conflicts_pallas(
    spec: SignatureSpec,
    sigs: jax.Array,
    addrs: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Fused hash -> membership-across-groups -> hit count, one kernel.

    ``sigs``: (G, num_words) uint32 packed group signatures; ``addrs``: (N,)
    touched ids.  Returns (N,) int32: for each address, the number of group
    signatures that contain it (LazySync flags a conflict when >= 2).
    """
    col, n = _address_column(addrs, block_n)
    rows = _i32(sigs)
    out = pl.pallas_call(
        functools.partial(_conflict_kernel, spec=spec),
        grid=(col.shape[0] // block_n,),
        in_specs=[_column_spec(block_n), _whole(rows.shape)],
        out_specs=_column_spec(block_n),
        out_shape=jax.ShapeDtypeStruct(col.shape, jnp.int32),
        interpret=interpret,
    )(col, rows)
    return out[:n, 0]


# ---------------------------------------------------------------------------
# Batched AND-prefilter (already word-level)
# ---------------------------------------------------------------------------


def _intersect_kernel(a_ref, b_ref, out_ref, *, spec: SignatureSpec):
    nonzero = (a_ref[...] & b_ref[...]) != 0  # (BLK_B, W)
    seg = jax.lax.broadcasted_iota(jnp.int32, nonzero.shape, 1) >> (
        spec.words_per_seg.bit_length() - 1)
    conflict = None
    for m in range(spec.num_segments):
        any_m = jnp.max(jnp.where(nonzero & (seg == m), 1, 0), axis=1,
                        keepdims=True)
        conflict = any_m if conflict is None else jnp.minimum(conflict, any_m)
    out_ref[...] = conflict


def bloom_intersect_pallas(
    spec: SignatureSpec,
    a: jax.Array,
    b: jax.Array,
    *,
    block_b: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Batched AND-prefilter: a, b (B, num_words) -> (B,) bool."""
    bsz = a.shape[0]
    pad = (-bsz) % block_b
    a, b = (jnp.pad(_i32(x), ((0, pad), (0, 0))) for x in (a, b))
    out = pl.pallas_call(
        functools.partial(_intersect_kernel, spec=spec),
        grid=(a.shape[0] // block_b,),
        in_specs=[pl.BlockSpec((block_b, spec.num_words), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(a, b)
    return out[:bsz, 0].astype(bool)


# ---------------------------------------------------------------------------
# Seed one-hot kernels (legacy): kept as the before/after baseline for the
# microbench and as a second implementation for differential testing.
# ---------------------------------------------------------------------------


def _insert_kernel_onehot(addr_ref, mask_ref, q_ref, out_ref, *, spec: SignatureSpec):
    step = pl.program_id(0)
    addrs = addr_ref[...]
    mask = mask_ref[...]
    pos = _h3_hash_block_xorfold(addrs, q_ref[...], spec)  # (BLK, M)
    pos = jnp.where(mask[:, None] > 0, pos, -1)
    # One-hot expand: (BLK*M, sig_bits) — scatter-as-compare on the VPU.
    tgt = jax.lax.broadcasted_iota(jnp.int32, (pos.size, spec.sig_bits), 1)
    hit = pos.reshape(-1, 1) == tgt
    bits = jnp.any(hit, axis=0)  # (sig_bits,)
    packed = bits.reshape(spec.num_words, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(packed << shifts[None, :], axis=1, dtype=jnp.uint32)
    prev = jnp.where(step == 0, jnp.zeros_like(words), out_ref[...])
    out_ref[...] = prev | words


def bloom_insert_pallas_onehot(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    mask: jax.Array | None = None,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Seed insert kernel: xor-fold hash + full-width one-hot expand."""
    addrs = addrs.reshape(-1).astype(jnp.uint32)
    n = addrs.shape[0]
    if mask is None:
        mask = jnp.ones((n,), dtype=jnp.int32)
    else:
        mask = mask.reshape(-1).astype(jnp.int32)
    pad = (-n) % block_n
    if pad:
        addrs = jnp.pad(addrs, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    n_pad = addrs.shape[0]
    q = jnp.asarray(spec.h3_matrix, dtype=jnp.uint32)
    grid = (n_pad // block_n,)
    delta = pl.pallas_call(
        functools.partial(_insert_kernel_onehot, spec=spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec(q.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((spec.num_words,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((spec.num_words,), jnp.uint32),
        interpret=interpret,
    )(addrs, mask, q)
    return sig | delta


def _query_kernel_onehot(addr_ref, q_ref, bits_ref, out_ref, *, spec: SignatureSpec):
    addrs = addr_ref[...]
    pos = _h3_hash_block_xorfold(addrs, q_ref[...], spec)  # (BLK, M)
    bits = bits_ref[...]  # (sig_bits,) int32 0/1
    # Gather-as-compare: member(n, m) = bits[pos[n, m]]
    blk = pos.shape[0]
    tgt = jax.lax.broadcasted_iota(
        jnp.int32, (blk * spec.num_segments, spec.sig_bits), 1
    )
    onehot = (pos.reshape(-1, 1) == tgt).astype(jnp.int32)
    looked_up = jnp.sum(onehot * bits[None, :], axis=1)  # (BLK*M,)
    member = jnp.all(looked_up.reshape(blk, spec.num_segments) > 0, axis=1)
    out_ref[...] = member.astype(jnp.int32)


def bloom_query_pallas_onehot(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Seed query kernel: xor-fold hash + one-hot gather over unpacked bits."""
    addrs_flat = addrs.reshape(-1).astype(jnp.uint32)
    n = addrs_flat.shape[0]
    pad = (-n) % block_n
    if pad:
        addrs_flat = jnp.pad(addrs_flat, (0, pad))
    n_pad = addrs_flat.shape[0]
    q = jnp.asarray(spec.h3_matrix, dtype=jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((sig[:, None] >> shifts) & np.uint32(1)).reshape(-1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_query_kernel_onehot, spec=spec),
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec(q.shape, lambda i: (0, 0)),
            pl.BlockSpec((spec.sig_bits,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(addrs_flat, q, bits)
    return out[:n].astype(bool)
