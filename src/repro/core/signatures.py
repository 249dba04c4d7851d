"""Parallel Bloom-filter coherence signatures (LazyPIM §5.3).

LazyPIM compresses the set of cache-line addresses touched by a PIM kernel
into fixed-width *parallel Bloom filters*: an N-bit signature is partitioned
into M segments, and each segment uses an independent H3 hash function that
maps an address to exactly one bit within the segment.  The paper uses
N = 2048 bits and M = 4 (``PIMReadSet``/``PIMWriteSet``), and a 16-register
bank of the same geometry for the ``CPUWriteSet``.

This module is the *bit-exact* software model of those hardware registers:
real H3 hashing, real collisions, real false positives.  Everything is pure
JAX so the coherence simulator can ``vmap``/``scan`` over it; the Pallas TPU
kernels in ``repro.kernels.bloom`` implement the same math for the hot batched
paths and are validated against this module.

**Byte-sliced H3 (the fast hot path).**  H3 is xor-linear over address bits:
``h_m(a) = XOR_{j : bit j of a set} Q[m, j]``.  Folding one bit at a time
costs ``addr_bits`` rounds of shift/and/select/xor.  Instead we precompute,
per 8-bit slice ``k`` of the address, a 256-entry table

    T[k][b][m] = XOR_{j : bit j of b set} Q[m, 8k + j]        (b in 0..255)

so that ``h_m(a) = T[0][a & 0xFF][m] ^ T[1][(a >> 8) & 0xFF][m] ^ ...`` —
four gathers and three XORs replace the 32-round fold, with *identical*
results (XOR associativity/commutativity; each address bit contributes its
``Q`` row exactly once either way).  The tables live on
:attr:`SignatureSpec.h3_tables` (built once per distinct spec via an
``lru_cache``, ~16 KB for the default geometry) and :func:`hash_positions`
uses them; :func:`hash_positions_xorfold` keeps the per-bit reference fold
for bit-exactness tests and before/after benchmarks
(``benchmarks/bench_signatures.py``).

Key signature properties used by the protocol (and tested in
``tests/test_signatures.py``):

* **No false negatives** — once inserted, an address always queries True, and
  two signatures sharing an address always intersect in every segment.
* **Sound AND-prefilter** — if any segment of ``a & b`` is empty, the two
  address sets are provably disjoint (paper §5.3).
* **Bounded false positives** — membership FP rate follows the partitioned
  Bloom-filter formula ``(1 - (1 - 1/seg_bits)**n)**M``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SignatureSpec",
    "default_spec",
    "empty_signature",
    "empty_bank",
    "hash_positions",
    "hash_positions_xorfold",
    "hash_with_tables",
    "insert",
    "insert_bank_round_robin",
    "query",
    "intersect",
    "intersect_nonempty",
    "bank_intersect_nonempty",
    "popcount",
    "saturation",
    "expected_membership_fp_rate",
    "pack_bits",
    "unpack_bits",
]


@dataclasses.dataclass(frozen=True)
class SignatureSpec:
    """Geometry + hash family of one coherence signature register.

    Defaults follow the paper: 2 Kbit register, M = 4 segments, H3 hashing
    (Sanchez et al. [39] via Bloom [6]).  ``addr_bits`` covers 32-bit
    cache-line addresses (the simulator uses line addresses, i.e. byte
    address >> 6, so 32 bits span a 256 GB physical space).
    """

    sig_bits: int = 2048
    num_segments: int = 4
    addr_bits: int = 32
    seed: int = 0xC0FFEE

    def __post_init__(self):
        if self.sig_bits % (32 * self.num_segments) != 0:
            raise ValueError(
                f"sig_bits={self.sig_bits} must be a multiple of "
                f"32*num_segments={32 * self.num_segments}"
            )
        seg = self.sig_bits // self.num_segments
        if seg & (seg - 1):
            # H3 XORs values < seg_bits; XOR is only closed under a
            # power-of-two bound.  A non-pow2 segment would hash some
            # addresses past the segment (and past sig_bits), producing
            # false negatives on insert+query.
            raise ValueError(
                f"seg_bits={seg} (sig_bits/num_segments) must be a power "
                f"of two for H3 hashing to stay in-segment"
            )

    @property
    def seg_bits(self) -> int:
        return self.sig_bits // self.num_segments

    @property
    def num_words(self) -> int:
        return self.sig_bits // 32

    @property
    def words_per_seg(self) -> int:
        return self.seg_bits // 32

    @property
    def num_byte_slices(self) -> int:
        return (self.addr_bits + 7) // 8

    @property
    def h3_matrix(self) -> np.ndarray:
        """H3 hash family: (num_segments, addr_bits) random values in
        [0, seg_bits).  h_m(a) = XOR_{j : bit j of a set} Q[m, j]."""
        return _h3_matrix(self)

    @property
    def h3_tables(self) -> np.ndarray:
        """Byte-sliced H3 lookup tables: (num_byte_slices, 256, num_segments)
        uint32, derived from :attr:`h3_matrix` (see module docstring).
        ``h(a) = XOR_k h3_tables[k, (a >> 8k) & 0xFF, :]`` — bit-exact with
        the per-bit xor-fold."""
        return _h3_tables(self)


@functools.lru_cache(maxsize=None)
def _h3_matrix(spec: SignatureSpec) -> np.ndarray:
    """Sample the H3 matrix once per *distinct* spec (specs are frozen and
    hashable, so equal specs constructed at different call sites share)."""
    rng = np.random.default_rng(spec.seed)
    q = rng.integers(
        0, spec.seg_bits, size=(spec.num_segments, spec.addr_bits)
    ).astype(np.uint32)
    q.setflags(write=False)
    return q


@functools.lru_cache(maxsize=None)
def _h3_tables(spec: SignatureSpec) -> np.ndarray:
    """Expand the H3 matrix into byte-sliced lookup tables (one-time, numpy)."""
    q = _h3_matrix(spec)  # (M, addr_bits)
    tabs = np.zeros((spec.num_byte_slices, 256, spec.num_segments), np.uint32)
    byte_vals = np.arange(256, dtype=np.uint32)
    for k in range(spec.num_byte_slices):
        for j in range(min(8, spec.addr_bits - 8 * k)):
            bit_set = ((byte_vals >> j) & 1).astype(bool)
            tabs[k] ^= np.where(bit_set[:, None], q[None, :, 8 * k + j], 0)
    tabs.setflags(write=False)
    return tabs


@functools.lru_cache(maxsize=None)
def _h3_tables_global(spec: SignatureSpec) -> np.ndarray:
    """Byte tables with the segment offsets pre-folded into slice 0 (hot
    path).  Hash values are < seg_bits and seg_bits is a power of two
    (enforced by ``__post_init__``), so the offset bits (m * seg_bits) are
    disjoint from the hash bits and survive the cross-slice XORs — OR-ing
    them into slice 0 makes :func:`hash_positions` emit *global* positions
    with zero extra ops."""
    tabs = _h3_tables(spec).copy()
    offs = (np.arange(spec.num_segments, dtype=np.uint32)
            * np.uint32(spec.seg_bits))
    tabs[0] |= offs[None, :]
    tabs.setflags(write=False)
    return tabs


@functools.lru_cache(maxsize=None)
def default_spec() -> SignatureSpec:
    """The paper-default spec as a shared singleton.  Call sites that would
    otherwise build ``SignatureSpec()`` ad hoc should use this so the cached
    H3 matrix/tables (and jit caches keyed on the spec) are reused."""
    return SignatureSpec()


def empty_signature(spec: SignatureSpec) -> jax.Array:
    """All-zero signature register, packed as (num_words,) uint32."""
    return jnp.zeros((spec.num_words,), dtype=jnp.uint32)


def empty_bank(spec: SignatureSpec, num_registers: int) -> jax.Array:
    """Bank of registers (the CPUWriteSet uses 16)."""
    return jnp.zeros((num_registers, spec.num_words), dtype=jnp.uint32)


def hash_positions(spec: SignatureSpec, addrs: jax.Array) -> jax.Array:
    """Global bit positions for each address: (N, num_segments) in
    [0, sig_bits).  Position = segment_offset + H3_m(address).

    Fast path: byte-sliced table lookups — ``num_byte_slices`` gathers
    (``jnp.take`` with clip-mode, the fast XLA lowering) and
    ``num_byte_slices - 1`` XORs, with the segment offsets pre-folded into
    the slice-0 table.  Bit-exact with :func:`hash_positions_xorfold`
    (tested in ``tests/test_signatures.py``).
    """
    addrs = addrs.astype(jnp.uint32).reshape(-1)
    return hash_with_tables(addrs, jnp.asarray(_h3_tables_global(spec)), spec)


def hash_with_tables(
    addrs: jax.Array, tabs: jax.Array, spec: SignatureSpec
) -> jax.Array:
    """Core byte-sliced lookup: (N,) uint32 addrs x (S, 256, M) tables ->
    (N, M) uint32 global positions.  ``tabs`` must be the offset-folded
    tables from :func:`_h3_tables_global`.  The Pallas kernels hash with
    the per-bit xor-fold instead (a table gather does not lower inside a
    TPU kernel); ``tests/test_bloom_word_kernels.py`` holds the two
    bit-exact."""
    h = jnp.take(tabs[0], addrs & np.uint32(0xFF), axis=0, mode="clip")
    for k in range(1, spec.num_byte_slices):
        byte = (addrs >> np.uint32(8 * k)) & np.uint32(0xFF)
        h = h ^ jnp.take(tabs[k], byte, axis=0, mode="clip")
    return h


def hash_positions_xorfold(spec: SignatureSpec, addrs: jax.Array) -> jax.Array:
    """Per-bit xor-fold H3 — the original (seed) implementation, kept as the
    reference for bit-exactness tests and the before/after microbench.
    ``addr_bits`` rounds of shift/and/select/xor."""
    addrs = addrs.astype(jnp.uint32).reshape(-1)
    q = jnp.asarray(spec.h3_matrix, dtype=jnp.uint32)  # (M, addr_bits)
    h = jnp.zeros((addrs.shape[0], spec.num_segments), dtype=jnp.uint32)
    for j in range(spec.addr_bits):
        bit = ((addrs >> np.uint32(j)) & np.uint32(1)).astype(bool)
        h = h ^ jnp.where(bit[:, None], q[None, :, j], np.uint32(0))
    seg_offsets = (
        jnp.arange(spec.num_segments, dtype=jnp.uint32) * np.uint32(spec.seg_bits)
    )
    return h + seg_offsets[None, :]


def pack_bits(spec: SignatureSpec, bits: jax.Array) -> jax.Array:
    """(sig_bits,) bool -> (num_words,) uint32 (little-endian bit order)."""
    b = bits.reshape(spec.num_words, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts[None, :], axis=1, dtype=jnp.uint32)


def unpack_bits(spec: SignatureSpec, words: jax.Array) -> jax.Array:
    """(..., num_words) uint32 -> (..., sig_bits) bool."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & np.uint32(1)
    return bits.reshape(*words.shape[:-1], spec.sig_bits).astype(bool)


def insert(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Insert a batch of addresses into a signature.

    ``mask`` (bool, same leading shape as ``addrs``) disables individual
    inserts — used by the simulator's fixed-width trace windows.
    """
    pos = hash_positions(spec, addrs).astype(jnp.int32)  # (N, M)
    if mask is not None:
        pos = jnp.where(mask.reshape(-1, 1), pos, spec.sig_bits)
    # Scatter into a bool staging array; duplicate indices are fine for set().
    staged = jnp.zeros((spec.sig_bits + 1,), dtype=bool)
    staged = staged.at[pos.reshape(-1)].set(True, mode="drop")
    return sig | pack_bits(spec, staged[: spec.sig_bits])


def insert_bank_round_robin(
    spec: SignatureSpec,
    bank: jax.Array,
    addrs: jax.Array,
    counter: jax.Array | int,
    mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """CPUWriteSet-style insertion: each address is round-robined into one of
    the bank's registers (paper §5.3).  Returns (new_bank, new_counter)."""
    num_regs = bank.shape[0]
    addrs = addrs.reshape(-1)
    n = addrs.shape[0]
    counter = jnp.asarray(counter, dtype=jnp.int32)
    if mask is None:
        mask = jnp.ones((n,), dtype=bool)
    mask = mask.reshape(-1)
    # Only valid inserts advance the round-robin pointer, like hardware would.
    offsets = jnp.cumsum(mask.astype(jnp.int32)) - mask.astype(jnp.int32)
    reg_ids = (counter + offsets) % num_regs
    pos = hash_positions(spec, addrs).astype(jnp.int32)  # (n, M)
    pos = jnp.where(mask[:, None], pos, spec.sig_bits)
    staged = jnp.zeros((num_regs, spec.sig_bits + 1), dtype=bool)
    reg_rep = jnp.repeat(reg_ids, spec.num_segments)
    staged = staged.at[reg_rep, pos.reshape(-1)].set(True, mode="drop")
    new_bank = bank | jax.vmap(lambda b: pack_bits(spec, b))(
        staged[:, : spec.sig_bits]
    )
    return new_bank, counter + jnp.sum(mask.astype(jnp.int32))


def query(spec: SignatureSpec, sig: jax.Array, addrs: jax.Array) -> jax.Array:
    """Membership test for a batch of addresses -> (N,) bool.

    No false negatives; false-positive rate per
    :func:`expected_membership_fp_rate`.
    """
    pos = hash_positions(spec, addrs).astype(jnp.int32)  # (N, M)
    bits = unpack_bits(spec, sig)  # (sig_bits,)
    return jnp.all(bits[pos], axis=-1)


def intersect(a: jax.Array, b: jax.Array) -> jax.Array:
    return a & b


def intersect_nonempty(spec: SignatureSpec, a: jax.Array, b: jax.Array) -> jax.Array:
    """Paper §5.3 conflict prefilter: True iff *every* segment of (a & b) has
    at least one bit set.  False => the address sets are provably disjoint."""
    inter = (a & b).reshape(spec.num_segments, spec.words_per_seg)
    return jnp.all(jnp.any(inter != 0, axis=1))


def bank_intersect_nonempty(
    spec: SignatureSpec, bank: jax.Array, sig: jax.Array
) -> jax.Array:
    """Prefilter a signature against every register of a bank -> scalar bool
    (True iff any register's intersection is all-segments-nonempty)."""
    return jnp.any(jax.vmap(lambda r: intersect_nonempty(spec, r, sig))(bank))


def popcount(words: jax.Array) -> jax.Array:
    """Number of set bits in a packed signature (any shape, summed)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & np.uint32(1)
    return jnp.sum(bits.astype(jnp.int32))


def saturation(spec: SignatureSpec, sig: jax.Array) -> jax.Array:
    """Fraction of bits set (Bloom-filter fill factor)."""
    return popcount(sig) / spec.sig_bits


def expected_membership_fp_rate(spec: SignatureSpec, n_inserted: int) -> float:
    """Theoretical membership false-positive rate of the partitioned Bloom
    filter after ``n_inserted`` distinct addresses."""
    fill = 1.0 - (1.0 - 1.0 / spec.seg_bits) ** n_inserted
    return float(fill**spec.num_segments)
