"""Trace → device tensors + shared bitmap/signature helpers for the simulator.

The coherence engine (``repro.core.mechanisms`` / ``repro.core.coherence``)
runs a ``lax.scan`` over partial-kernel windows.  This module prepares the
static per-trace tensors (padded access lists, per-line H3 hash positions,
pre-write bitmaps, unique-line counts) and the primitives every mechanism
shares.

**Packed word layout (the hot path).**  Every per-line bitmap the simulator
carries through the scan (``present``, ``dirty``, ``cpuws``, ``conc``,
``read_bm``, the per-kernel ``pre_writes``) is a ``ceil(num_lines / 32)``
array of ``uint32`` words — bit ``b`` of word ``w`` is line ``32 * w + b``
(little-endian bit order, matching :func:`repro.core.signatures.pack_bits`).
Bloom images (``read_bits`` / ``write_bits``, the CPUWriteSet bank) are
``sig_bits / 32`` words with the same convention.  Pad bits past
``num_lines`` are **always zero**; every primitive preserves that invariant
(negation only ever appears as ``x & ~y`` against a clean bitmap).  The
packed carry is 32× smaller than the boolean seed carry and all bitmap
algebra (OR/AND/select/popcount) runs word-wise:

* ``scatter_set``          — OR line ids into a packed bitmap (sort + dedupe +
                             distinct-bit add ⇒ O(A log A), not O(num_lines))
* ``gather_hits``          — per-slot membership test for an address list
* ``sig_bits_from_ids``    — packed Bloom image of an address list
* ``sig_bits_from_bitmap`` — packed Bloom image of a packed bitmap
* ``bank_bits_from_bitmap``— packed CPUWriteSet register bank
* ``conflict_any``         — paper §5.3 AND-prefilter over segment-aligned
                             word masks
* ``line_sig_hits``        — per-(segment, line) signature bit lookups; the
                             shared lookup behind ``members`` and
                             ``conflict_from_hits``
* ``members``              — packed membership mask (with real H3 FPs)
* ``conflict_from_hits``   — ``conflict_any∘bank_bits_from_bitmap`` fused
                             into a lookup + mod-``R`` segment reduction
                             (no scatter); bit-exact with the unfused pair
* ``evict_to_cap``         — capacity eviction via word popcounts
* ``cpu_cache_step``       — CPU-side presence/dirty word-bitmap evolution

Each primitive keeps its boolean seed implementation as a ``*_bool``
reference (same math on ``(num_lines,)`` bool bitmaps); the differential
tests in ``tests/test_packed_engine.py`` assert bit-exact equality between
the two families, and ``repro.core._boolref`` runs the full seed simulators
on the ``*_bool`` path.

Everything is bit-exact with :mod:`repro.core.signatures` (same H3 matrices);
the simulator's false positives are *actual* hash collisions.

**Geometry bucketing (the fleet batch engine's prep layer).**  A whole
workload fleet runs through a handful of compiled scans instead of one per
geometry: :func:`bucket_bound` rounds line counts up pow2-ish,
:func:`pad_trace` pads a prepared trace to a bucket shape under explicit
validity (padded lines never enter a bitmap or signature, padded windows
are marked in ``window_valid`` and leave every scan carry untouched), and
:func:`bucket_traces` groups a fleet into those buckets —
``repro.sim.engine.run_batch`` vmaps one compiled scan per (mechanism,
bucket) over the stacked workload axis, bit-exact with the sequential path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.signatures import SignatureSpec, default_spec, hash_positions
from repro.sim.costmodel import HWParams, LINE_BYTES
from repro.sim.trace import WindowTrace

CPUWS_REGS = 16  # CPUWriteSet bank registers (paper §5.7)

# Multiplicative-hash constants shared by the deterministic per-(line, window)
# thinning hashes (capacity eviction in :func:`evict_to_cap`, PIM-DBI drain in
# ``repro.core.coherence``).  Named once so the two sites cannot drift.
KNUTH_MULT = np.uint32(2654435761)   # 2**32 / golden ratio (Knuth §6.4)
KNUTH_STEP = np.uint32(40503)        # Knuth's 16-bit multiplicative constant
XXH_PRIME2 = np.uint32(2246822519)   # xxHash32 PRIME32_2
XXH_PRIME5 = np.uint32(374761393)    # xxHash32 PRIME32_5


def line_window_u01(
    num_lines: int, window_idx: jax.Array, mult: np.uint32, step: np.uint32
) -> jax.Array:
    """Deterministic per-(line, window) uniform in [0, 1): a multiplicative
    hash of the line id stepped by the window index, top 16 bits scaled.
    Both thinning sites (eviction, DBI drain) share this kernel with their
    own (mult, step) constants."""
    h = (jnp.arange(num_lines, dtype=jnp.uint32) * mult
         + window_idx.astype(jnp.uint32) * step)
    return ((h >> np.uint32(16)) & np.uint32(0xFFFF)).astype(jnp.float32) / 65536.0


# Static metadata vs tensor leaves of TraceTensors — the single source of
# truth for both the pytree registration and engine.stack_traces.
# ``cpu_priv_miss_rate``/``cpu_reuse`` are *traced* scalar leaves (not
# static): workloads that differ only in their locality constants share one
# compiled step and can ride in one geometry bucket (engine.run_batch).
TRACE_META_FIELDS = ("name", "threads", "num_lines", "num_windows",
                     "num_kernels", "spec")
TRACE_DATA_FIELDS = ("line_pos", "line_reg", "pim_reads", "pim_writes",
                     "cpu_reads", "cpu_writes", "pim_r_valid", "pim_w_valid",
                     "cpu_r_valid", "cpu_w_valid", "kernel_id", "kernel_start",
                     "kernel_end", "pre_writes", "pre_writes_words",
                     "pim_instr", "cpu_instr", "cpu_priv", "pim_uniq_r",
                     "pim_uniq_w", "pim_uniq", "cpu_priv_miss_rate",
                     "cpu_reuse", "window_valid")


@functools.partial(
    jax.tree_util.register_dataclass,
    meta_fields=TRACE_META_FIELDS,
    data_fields=TRACE_DATA_FIELDS,
)
@dataclasses.dataclass(frozen=True)
class TraceTensors:
    """Device-resident, fixed-shape view of one WindowTrace (a jit pytree:
    tensors are leaves, geometry/spec are static metadata)."""

    name: str
    threads: int
    num_lines: int
    num_windows: int
    num_kernels: int
    spec: SignatureSpec

    # Per-line static tables
    line_pos: jax.Array      # (M, num_lines) int32 global signature bit positions
    line_reg: jax.Array      # (num_lines,) int32 CPUWriteSet register id

    # Access lists (−1 = empty slot) + validity masks
    pim_reads: jax.Array     # (W, AR) int32
    pim_writes: jax.Array    # (W, AW) int32
    cpu_reads: jax.Array     # (W, BR) int32
    cpu_writes: jax.Array    # (W, BW) int32
    pim_r_valid: jax.Array   # (W, AR) bool
    pim_w_valid: jax.Array   # (W, AW) bool
    cpu_r_valid: jax.Array   # (W, BR) bool
    cpu_w_valid: jax.Array   # (W, BW) bool

    # Kernel structure
    kernel_id: jax.Array     # (W,) int32
    kernel_start: jax.Array  # (W,) bool
    kernel_end: jax.Array    # (W,) bool
    pre_writes: jax.Array    # (K, num_lines) bool (boolean reference path)
    pre_writes_words: jax.Array  # (K, ceil(num_lines/32)) uint32 (packed path)

    # Work counts
    pim_instr: jax.Array     # (W,) f32
    cpu_instr: jax.Array     # (W,) f32
    cpu_priv: jax.Array      # (W,) f32
    cpu_priv_miss_rate: jax.Array  # () f32 traced scalar
    cpu_reuse: jax.Array           # () f32 traced scalar

    # Unique-line counts per window (locality model inputs)
    pim_uniq_r: jax.Array    # (W,) f32
    pim_uniq_w: jax.Array    # (W,) f32
    pim_uniq: jax.Array      # (W,) f32 (reads ∪ writes)

    # Padding validity: False marks windows appended by :func:`pad_trace`.
    # Every mechanism step passes its carry through unchanged (and
    # accumulates nothing) on an invalid window.
    window_valid: jax.Array  # (W,) bool

    @property
    def sig_bits(self) -> int:
        return self.spec.sig_bits

    @property
    def num_segments(self) -> int:
        return self.spec.num_segments

    @property
    def num_line_words(self) -> int:
        """Packed line-bitmap width: ceil(num_lines / 32) uint32 words."""
        return (self.num_lines + 31) // 32

    @property
    def sig_words(self) -> int:
        """Packed Bloom-image width: sig_bits / 32 uint32 words."""
        return self.spec.num_words


# ---------------------------------------------------------------------------
# Packed bitmap core (uint32 words, little-endian bit order, zero pad bits)
# ---------------------------------------------------------------------------


def packed_words(nbits: int) -> int:
    return (nbits + 31) // 32


def pack_bitmap(bits: jax.Array) -> jax.Array:
    """(n,) bool -> (ceil(n/32),) uint32.  Pad bits are zero."""
    n = bits.shape[0]
    pad = (-n) % 32
    b = jnp.pad(bits, (0, pad)).reshape(-1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts[None, :], axis=1, dtype=jnp.uint32)


def unpack_bitmap(words: jax.Array, nbits: int) -> jax.Array:
    """(..., nw) uint32 -> (..., nbits) bool."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & np.uint32(1)
    return bits.reshape(*words.shape[:-1], -1)[..., :nbits].astype(bool)


def popcount_words(words: jax.Array) -> jax.Array:
    """Total set-bit count of a packed bitmap (SWAR popcount, int32 scalar)."""
    w = words
    w = w - ((w >> np.uint32(1)) & np.uint32(0x55555555))
    w = (w & np.uint32(0x33333333)) + ((w >> np.uint32(2)) & np.uint32(0x33333333))
    w = (w + (w >> np.uint32(4))) & np.uint32(0x0F0F0F0F)
    per_word = (w * np.uint32(0x01010101)) >> np.uint32(24)
    return jnp.sum(per_word.astype(jnp.int32))


def scatter_set(
    words: jax.Array,
    ids: jax.Array,
    valid: jax.Array | None,
    nbits: int,
) -> jax.Array:
    """OR the valid line ids of ``ids`` into a packed bitmap.

    O(A log A) in the id-list length: sort the ids, keep the first of each
    duplicate run, then scatter-*add* single-bit masks — after dedup every
    surviving update targets a distinct bit, so integer add is exactly OR
    (no carries).  The seed path (:func:`scatter_set_bool`) instead memsets
    and scatters an O(num_lines) boolean staging array per call.
    """
    ids = ids.reshape(-1)
    if valid is None:
        p = ids
    else:
        p = jnp.where(valid.reshape(-1), ids, nbits)
    p = jnp.sort(p)
    fresh = jnp.concatenate([jnp.ones((1,), bool), p[1:] != p[:-1]])
    # Negative ids (the repo-wide -1 padding sentinel) must be dropped here,
    # not wrapped: a negative scatter index would land in the last word.
    keep = fresh & (p >= 0) & (p < nbits)
    word = jnp.where(keep, p >> 5, words.shape[0])
    mask = jnp.where(keep, jnp.uint32(1) << (p & 31).astype(jnp.uint32),
                     jnp.uint32(0))
    delta = jnp.zeros_like(words).at[word].add(mask, mode="drop")
    return words | delta


def gather_hits(words: jax.Array, ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-slot hit flags: valid & line present (packed lookup)."""
    idx = jnp.clip(ids, 0, words.shape[0] * 32 - 1)
    w = words[idx >> 5]
    return valid & (((w >> (idx & 31).astype(jnp.uint32)) & 1) != 0)


# ---------------------------------------------------------------------------
# Signature primitives over line-id tensors (bit-exact with core.signatures)
# ---------------------------------------------------------------------------


def _ids_pos(tt: TraceTensors, ids: jax.Array) -> jax.Array:
    """(M, A) signature positions of the line ids (A,): one 1-D gather per
    segment row, so the compiler never re-lays the (M, num_lines) table
    out with its short segment axis minor (32x padded on the TPU)."""
    idx = jnp.clip(ids, 0, tt.num_lines - 1)
    return jnp.stack([tt.line_pos[m][idx] for m in range(tt.num_segments)])


def sig_bits_from_ids(
    tt: TraceTensors, ids: jax.Array, valid: jax.Array
) -> jax.Array:
    """Packed Bloom image (sig_words,) uint32 of the valid line ids (A,)."""
    pos = jnp.where(valid[None, :], _ids_pos(tt, ids), tt.sig_bits)
    return scatter_set(jnp.zeros((tt.sig_words,), jnp.uint32),
                       pos.reshape(-1), None, tt.sig_bits)


def sig_bits_from_bitmap(tt: TraceTensors, words: jax.Array) -> jax.Array:
    """Packed Bloom image (sig_words,) uint32 of all lines set in a packed
    bitmap.  Inherently O(num_lines · M): every set line contributes its M
    static hash positions."""
    bitmap = unpack_bitmap(words, tt.num_lines)
    return pack_bitmap(_sig_image_bool(tt, bitmap))


def bank_bits_from_bitmap(
    tt: TraceTensors, words: jax.Array, num_regs: int = CPUWS_REGS
) -> jax.Array:
    """Packed CPUWriteSet bank (num_regs, sig_words) uint32 from a packed
    dirty-line bitmap.  Register assignment is line_id % num_regs — the
    deterministic equivalent of the paper's round-robin pointer for
    set-valued (unordered) insertion.  The simulators use the fused
    :func:`conflict_from_hits` instead of materializing the bank."""
    bitmap = unpack_bitmap(words, tt.num_lines)
    bank = _bank_image_bool(tt, bitmap, num_regs)
    return jax.vmap(pack_bitmap)(bank)


def conflict_any(tt: TraceTensors, read_words: jax.Array, bank_words: jax.Array) -> jax.Array:
    """Paper §5.3/§5.5 conflict prefilter: True iff the PIMReadSet intersects
    ANY CPUWriteSet register with every segment non-empty.  Segments are
    word-aligned (sig_bits is a multiple of 32 · num_segments), so the test
    is word-mask algebra."""
    inter = bank_words & read_words[None, :]  # (R, sig_words)
    seg = inter.reshape(bank_words.shape[0], tt.num_segments, -1)
    return jnp.any(jnp.all(jnp.any(seg != 0, axis=2), axis=1))


def line_sig_hits(tt: TraceTensors, sig_words: jax.Array) -> jax.Array:
    """Per-(segment, line) signature bit lookups -> (M, num_lines) bool.

    One lookup of the packed image serves every consumer in a simulator
    step: ``members`` is the all-segments AND, ``conflict_from_hits`` the
    per-register segment OR — so the packed LazyPIM step looks each image
    up once instead of once per membership/bank call.

    Lines run along the minor axis and the word lookup is a select chain
    over the segment's ``words_per_seg`` words, not a gather: row ``m`` of
    ``line_pos`` lies in segment ``m``.  A ``(num_lines, M)`` gather pads
    its minor axis of 4 to the TPU's 128 lanes (32x the bytes) and runs
    element by element."""
    pos = tt.line_pos  # (M, n) int32 global positions, segment m in row m
    wps = tt.spec.words_per_seg
    seg_words = sig_words.reshape(tt.num_segments, wps)
    local = (pos >> 5) & (wps - 1)  # word within the segment (wps is pow2)
    w = jnp.zeros(pos.shape, jnp.uint32)
    for j in range(wps):
        w = jnp.where(local == j, seg_words[:, j:j + 1], w)
    return ((w >> (pos & 31).astype(jnp.uint32)) & 1) != 0


def members(tt: TraceTensors, words: jax.Array, sig_words: jax.Array) -> jax.Array:
    """Packed per-line signature membership mask for lines set in ``words``.
    Includes the signature's real false positives."""
    return members_from_hits(words, line_sig_hits(tt, sig_words))


def members_from_hits(words: jax.Array, hits: jax.Array) -> jax.Array:
    """``members`` given a precomputed :func:`line_sig_hits` lookup."""
    return words & pack_bitmap(jnp.all(hits, axis=0))


def conflict_from_hits(
    tt: TraceTensors,
    words: jax.Array,
    hits: jax.Array,
    num_regs: int = CPUWS_REGS,
) -> jax.Array:
    """``conflict_any(tt, sig, bank_bits_from_bitmap(tt, words))`` without
    building the bank: segment ``m`` of register ``r``'s intersection with
    the read image is non-empty iff some line ``i ≡ r (mod num_regs)`` set
    in ``words`` has its segment-``m`` hash bit set in the image — each
    line's M positions land in M distinct segments, so the bank scatter
    collapses to a lookup (``hits``) plus a mod-``num_regs`` any-reduction.
    The reduction first folds rows of ``group`` lines (a multiple of
    ``num_regs`` near the TPU's 128 lanes), so no axis as narrow as
    ``num_regs`` is ever the minor one of a num_lines-sized array.
    Bit-exact with the unfused pair (differentially tested)."""
    n = tt.num_lines
    masked = hits & unpack_bitmap(words, n)[None, :]  # (M, n)
    group = num_regs * max(1, 128 // num_regs)
    masked = jnp.pad(masked, ((0, 0), (0, (-n) % group)))
    rows = jnp.any(masked.reshape(tt.num_segments, -1, group), axis=1)
    seg_any = jnp.any(rows.reshape(tt.num_segments, -1, num_regs), axis=1)
    return jnp.any(jnp.all(seg_any, axis=0))


def ids_member(
    tt: TraceTensors, ids: jax.Array, valid: jax.Array, sig_words: jax.Array
) -> jax.Array:
    """Signature membership for an address list (A,) -> (A,) bool."""
    pos = _ids_pos(tt, ids)
    w = sig_words[pos >> 5]
    hit = ((w >> (pos & 31).astype(jnp.uint32)) & 1) != 0
    return valid & jnp.all(hit, axis=0)


# ---------------------------------------------------------------------------
# CPU cache bitmap evolution (packed)
# ---------------------------------------------------------------------------


def evict_to_cap(
    present: jax.Array,
    dirty: jax.Array,
    window_idx: jax.Array,
    cap,
    nbits: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Capacity model: thin the packed presence bitmap down to ~cap lines
    using the deterministic per-(line, window) hash.  Evicted dirty lines are
    written back (returned as a count).  No-op when under cap."""
    count = popcount_words(present)
    over = count > cap
    keep_prob = jnp.clip(cap / jnp.maximum(count, 1), 0.0, 1.0)
    u = line_window_u01(nbits, window_idx, KNUTH_MULT, KNUTH_STEP)
    over_mask = jnp.where(over, np.uint32(0xFFFFFFFF), np.uint32(0))
    drop = present & pack_bitmap(u > keep_prob) & over_mask
    wb_lines = popcount_words(dirty & drop).astype(jnp.float32)
    return present & ~drop, dirty & ~drop, wb_lines


@dataclasses.dataclass
class CpuStepOut:
    present: jax.Array
    dirty: jax.Array
    hits: jax.Array        # scalar f32
    misses: jax.Array      # scalar f32
    wb_lines: jax.Array    # capacity writebacks, f32
    mem_ns: jax.Array      # CPU-side memory latency for this window
    fill_bytes: jax.Array  # off-chip fill traffic (miss fills)


def cpu_cache_step(
    tt: TraceTensors,
    hw: HWParams,
    present: jax.Array,
    dirty: jax.Array,
    w: jax.Array,
    *,
    cacheable: bool = True,
    cap_lines=None,
) -> CpuStepOut:
    """One window of CPU-thread accesses to the PIM data region, on packed
    word bitmaps.

    ``cacheable=False`` models NC: every access is an off-chip DRAM access,
    and the presence/dirty bitmaps stay empty.
    """
    cr, crv = tt.cpu_reads[w], tt.cpu_r_valid[w]
    cw, cwv = tt.cpu_writes[w], tt.cpu_w_valid[w]
    n_acc = (jnp.sum(crv) + jnp.sum(cwv)).astype(jnp.float32)
    reuse = tt.cpu_reuse
    miss_ns = hw.offchip_mem_ns / hw.cpu_mlp  # OoO overlaps misses

    if not cacheable:
        # NC: every dynamic access (first touch AND repeats) goes to DRAM.
        n_dyn = n_acc * reuse
        mem_ns = n_dyn * miss_ns / hw.cpu_cores
        fill = n_dyn * hw.nc_bytes
        zero = jnp.zeros((), jnp.float32)
        return CpuStepOut(present, dirty, zero, n_dyn, zero, mem_ns, fill)

    r_hit = gather_hits(present, cr, crv)
    w_hit = gather_hits(present, cw, cwv)
    misses = (jnp.sum(crv & ~r_hit) + jnp.sum(cwv & ~w_hit)).astype(jnp.float32)
    hits = (jnp.sum(r_hit) + jnp.sum(w_hit)).astype(jnp.float32)
    present = scatter_set(present, cr, crv, tt.num_lines)
    present = scatter_set(present, cw, cwv, tt.num_lines)
    dirty = scatter_set(dirty, cw, cwv, tt.num_lines)
    cap = cap_lines if cap_lines is not None else hw.thread_cache_cap
    present, dirty, wb = evict_to_cap(present, dirty, w, cap, tt.num_lines)
    # first touches: L2 hit or off-chip miss; repeats: L1 hits.
    repeats_ns = n_acc * (reuse - 1.0) * hw.l1_hit_ns
    mem_ns = (hits * hw.l2_hit_ns + misses * miss_ns + repeats_ns) / hw.cpu_cores
    fill = (misses + wb) * LINE_BYTES
    return CpuStepOut(present, dirty, hits, misses, wb, mem_ns, fill)


# ---------------------------------------------------------------------------
# Boolean seed reference path (*_bool): same math on (num_lines,) bool
# bitmaps.  Kept verbatim for the differential tests (packed vs boolean
# SimResult equality) and as the readable specification of each primitive.
# ---------------------------------------------------------------------------


def _sig_image_bool(tt: TraceTensors, bitmap: jax.Array) -> jax.Array:
    pos = jnp.where(bitmap[None, :], tt.line_pos, tt.sig_bits)  # (M, n)
    staged = jnp.zeros((tt.sig_bits + 1,), dtype=bool)
    staged = staged.at[pos.reshape(-1)].set(True, mode="drop")
    return staged[: tt.sig_bits]


def _bank_image_bool(
    tt: TraceTensors, bitmap: jax.Array, num_regs: int
) -> jax.Array:
    stride = tt.sig_bits + 1
    pos = jnp.where(bitmap[None, :], tt.line_pos, tt.sig_bits)  # (M, n)
    flat = tt.line_reg[None, :] * stride + pos  # (M, n)
    staged = jnp.zeros((num_regs * stride,), dtype=bool)
    staged = staged.at[flat.reshape(-1)].set(True, mode="drop")
    return staged.reshape(num_regs, stride)[:, : tt.sig_bits]


def sig_bits_from_ids_bool(
    tt: TraceTensors, ids: jax.Array, valid: jax.Array
) -> jax.Array:
    """Bloom image (sig_bits,) bool of the valid line ids in ``ids`` (A,)."""
    pos = tt.line_pos[:, jnp.clip(ids, 0, tt.num_lines - 1)]  # (M, A)
    pos = jnp.where(valid[None, :], pos, tt.sig_bits)
    staged = jnp.zeros((tt.sig_bits + 1,), dtype=bool)
    staged = staged.at[pos.reshape(-1)].set(True, mode="drop")
    return staged[: tt.sig_bits]


def sig_bits_from_bitmap_bool(tt: TraceTensors, bitmap: jax.Array) -> jax.Array:
    """Bloom image (sig_bits,) bool of all lines set in ``bitmap`` (n,) bool."""
    return _sig_image_bool(tt, bitmap)


def bank_bits_from_bitmap_bool(
    tt: TraceTensors, bitmap: jax.Array, num_regs: int = CPUWS_REGS
) -> jax.Array:
    """CPUWriteSet bank (num_regs, sig_bits) bool from a dirty-line bitmap."""
    return _bank_image_bool(tt, bitmap, num_regs)


def conflict_any_bool(
    tt: TraceTensors, read_bits: jax.Array, bank_bits: jax.Array
) -> jax.Array:
    """Boolean-image conflict prefilter (seed reference)."""
    inter = bank_bits & read_bits[None, :]  # (R, sig_bits)
    seg = inter.reshape(bank_bits.shape[0], tt.num_segments, -1)
    return jnp.any(jnp.all(jnp.any(seg, axis=2), axis=1))


def members_bool(tt: TraceTensors, bitmap: jax.Array, bits: jax.Array) -> jax.Array:
    """Per-line signature membership (n,) bool for lines set in ``bitmap``."""
    looked = bits[tt.line_pos]  # (M, n)
    return bitmap & jnp.all(looked, axis=0)


def ids_member_bool(
    tt: TraceTensors, ids: jax.Array, valid: jax.Array, bits: jax.Array
) -> jax.Array:
    """Signature membership for an address list against a boolean image."""
    pos = tt.line_pos[:, jnp.clip(ids, 0, tt.num_lines - 1)]  # (M, A)
    return valid & jnp.all(bits[pos], axis=0)


def scatter_set_bool(bitmap: jax.Array, ids: jax.Array, valid: jax.Array) -> jax.Array:
    """OR line ids into a boolean bitmap.  Invalid slots are redirected to
    the (out-of-bounds) index ``n`` and dropped by the scatter itself."""
    idx = jnp.where(valid, ids, bitmap.shape[0])
    return bitmap.at[idx].set(True, mode="drop")


def gather_hits_bool(bitmap: jax.Array, ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-slot hit flags: valid & line present."""
    present = bitmap[jnp.clip(ids, 0, bitmap.shape[0] - 1)]
    return valid & present


def evict_to_cap_bool(
    present: jax.Array,
    dirty: jax.Array,
    window_idx: jax.Array,
    cap,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Boolean-bitmap capacity eviction (seed reference)."""
    n = present.shape[0]
    count = jnp.sum(present)
    over = count > cap
    keep_prob = jnp.clip(cap / jnp.maximum(count, 1), 0.0, 1.0)
    u = line_window_u01(n, window_idx, KNUTH_MULT, KNUTH_STEP)
    drop = present & (u > keep_prob) & over
    wb_lines = jnp.sum(dirty & drop).astype(jnp.float32)
    return present & ~drop, dirty & ~drop, wb_lines


def cpu_cache_step_bool(
    tt: TraceTensors,
    hw: HWParams,
    present: jax.Array,
    dirty: jax.Array,
    w: jax.Array,
    *,
    cacheable: bool = True,
    cap_lines=None,
) -> CpuStepOut:
    """Boolean-bitmap CPU cache step (seed reference)."""
    cr, crv = tt.cpu_reads[w], tt.cpu_r_valid[w]
    cw, cwv = tt.cpu_writes[w], tt.cpu_w_valid[w]
    n_acc = (jnp.sum(crv) + jnp.sum(cwv)).astype(jnp.float32)
    reuse = tt.cpu_reuse
    miss_ns = hw.offchip_mem_ns / hw.cpu_mlp

    if not cacheable:
        n_dyn = n_acc * reuse
        mem_ns = n_dyn * miss_ns / hw.cpu_cores
        fill = n_dyn * hw.nc_bytes
        zero = jnp.zeros((), jnp.float32)
        return CpuStepOut(present, dirty, zero, n_dyn, zero, mem_ns, fill)

    r_hit = gather_hits_bool(present, cr, crv)
    w_hit = gather_hits_bool(present, cw, cwv)
    misses = (jnp.sum(crv & ~r_hit) + jnp.sum(cwv & ~w_hit)).astype(jnp.float32)
    hits = (jnp.sum(r_hit) + jnp.sum(w_hit)).astype(jnp.float32)
    present = scatter_set_bool(present, cr, crv)
    present = scatter_set_bool(present, cw, cwv)
    dirty = scatter_set_bool(dirty, cw, cwv)
    cap = cap_lines if cap_lines is not None else hw.thread_cache_cap
    present, dirty, wb = evict_to_cap_bool(present, dirty, w, cap)
    repeats_ns = n_acc * (reuse - 1.0) * hw.l1_hit_ns
    mem_ns = (hits * hw.l2_hit_ns + misses * miss_ns + repeats_ns) / hw.cpu_cores
    fill = (misses + wb) * LINE_BYTES
    return CpuStepOut(present, dirty, hits, misses, wb, mem_ns, fill)


# ---------------------------------------------------------------------------
# Trace staging
# ---------------------------------------------------------------------------


def _uniq_count_loop(rows: np.ndarray) -> np.ndarray:
    """Per-row unique-count, reference Python loop (seed implementation)."""
    out = np.empty((rows.shape[0],), dtype=np.float32)
    for i, row in enumerate(rows):
        v = row[row >= 0]
        out[i] = len(np.unique(v))
    return out


def _uniq_union_count_loop(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Per-row unique-union-count, reference Python loop (seed)."""
    out = np.empty((a.shape[0],), dtype=np.float32)
    for i in range(a.shape[0]):
        va = a[i][a[i] >= 0]
        vb = b[i][b[i] >= 0]
        out[i] = len(np.unique(np.concatenate([va, vb])))
    return out


def _uniq_count(rows: np.ndarray) -> np.ndarray:
    """Vectorized per-row unique-count of the non-negative entries.

    Row-wise sort pushes the −1 padding to the front; an entry counts iff it
    is valid and differs from its left neighbor (the first valid entry in a
    row always differs from −1).  Equal to :func:`_uniq_count_loop` without
    the O(W) interpreter round-trips at trace-prep time."""
    s = np.sort(rows, axis=1)
    valid = s >= 0
    first = np.empty_like(valid)
    first[:, :1] = valid[:, :1]
    first[:, 1:] = valid[:, 1:] & (s[:, 1:] != s[:, :-1])
    return first.sum(axis=1).astype(np.float32)


def _uniq_union_count(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized per-row unique-count of the union of two padded id lists."""
    return _uniq_count(np.concatenate([a, b], axis=1))


def _pack_rows_np(bits: np.ndarray) -> np.ndarray:
    """(..., n) bool -> (..., ceil(n/32)) uint32, same bit order as
    :func:`pack_bitmap` (numpy, prepare-time)."""
    n = bits.shape[-1]
    pad = (-n) % 32
    if pad:
        widths = [(0, 0)] * (bits.ndim - 1) + [(0, pad)]
        bits = np.pad(bits, widths)
    b = bits.reshape(*bits.shape[:-1], -1, 32).astype(np.uint32)
    return (b << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint64).astype(np.uint32)


def line_positions(spec: SignatureSpec, start: int, stop: int) -> jax.Array:
    """H3 positions of lines ``start..stop-1`` as the (M, stop - start)
    int32 ``line_pos`` table: lines along the minor axis, so the per-line
    work of a simulator step is lane-dense on the TPU."""
    ids = jnp.arange(start, stop, dtype=jnp.uint32)
    return hash_positions(spec, ids).astype(jnp.int32).T


def prepare(trace: WindowTrace, spec: SignatureSpec | None = None) -> TraceTensors:
    """Stage a WindowTrace onto device with precomputed hash tables.

    Accepts numpy- or device-backed traces (the JAX synthesis path of
    ``repro.sim.synth`` hands over device arrays); each access-list field
    is normalized to host numpy exactly once, so the derived host-side
    tensors (validity masks, packed pre-writes, unique-line counts) don't
    re-trigger a device transfer per use.

    Uses the shared :func:`default_spec` singleton when no spec is given so
    the byte-sliced H3 tables (and every jit cache keyed on the spec, which
    is static TraceTensors metadata) are reused across traces."""
    spec = spec or default_spec()
    n = trace.num_lines
    pim_reads = np.asarray(trace.pim_reads)
    pim_writes = np.asarray(trace.pim_writes)
    cpu_reads = np.asarray(trace.cpu_reads)
    cpu_writes = np.asarray(trace.cpu_writes)
    pre_writes = np.asarray(trace.pre_writes)
    # Byte-sliced H3 positions for every line in the PIM data region
    # (one-time; hash_positions is the fast table-lookup path).
    line_pos = line_positions(spec, 0, n)
    line_reg = (jnp.arange(n, dtype=jnp.int32)) % CPUWS_REGS

    def dev(x, dt=jnp.int32):
        return jnp.asarray(x, dtype=dt)

    return TraceTensors(
        name=trace.name,
        threads=trace.threads,
        num_lines=n,
        num_windows=trace.num_windows,
        num_kernels=trace.num_kernels,
        spec=spec,
        line_pos=line_pos,
        line_reg=line_reg,
        pim_reads=dev(pim_reads),
        pim_writes=dev(pim_writes),
        cpu_reads=dev(cpu_reads),
        cpu_writes=dev(cpu_writes),
        pim_r_valid=dev(pim_reads >= 0, jnp.bool_),
        pim_w_valid=dev(pim_writes >= 0, jnp.bool_),
        cpu_r_valid=dev(cpu_reads >= 0, jnp.bool_),
        cpu_w_valid=dev(cpu_writes >= 0, jnp.bool_),
        kernel_id=dev(trace.kernel_id),
        kernel_start=dev(trace.kernel_start, jnp.bool_),
        kernel_end=dev(trace.kernel_end, jnp.bool_),
        pre_writes=dev(pre_writes, jnp.bool_),
        pre_writes_words=dev(_pack_rows_np(pre_writes), jnp.uint32),
        pim_instr=dev(trace.pim_instr, jnp.float32),
        cpu_instr=dev(trace.cpu_instr, jnp.float32),
        cpu_priv=dev(trace.cpu_priv_accesses, jnp.float32),
        cpu_priv_miss_rate=dev(float(trace.cpu_priv_miss_rate), jnp.float32),
        cpu_reuse=dev(float(trace.cpu_reuse), jnp.float32),
        pim_uniq_r=dev(_uniq_count(pim_reads), jnp.float32),
        pim_uniq_w=dev(_uniq_count(pim_writes), jnp.float32),
        pim_uniq=dev(_uniq_union_count(pim_reads, pim_writes), jnp.float32),
        window_valid=jnp.ones((trace.num_windows,), dtype=jnp.bool_),
    )


def neutral_trace(tt: TraceTensors) -> TraceTensors:
    """Strip presentation-only metadata (``name``/``threads``) before a jit
    call.  Both are static pytree metadata, so they key the jit cache: two
    same-geometry workloads would otherwise compile the identical scan twice
    (the pre-batching fig7 wall was one XLA compile per *workload* per
    mechanism, not per geometry).  Results are finalized with the original
    trace's name by the caller."""
    if tt.name == "" and tt.threads == 0:
        return tt
    return dataclasses.replace(tt, name="", threads=0)


def dummy_trace(spec: SignatureSpec, *, num_lines: int, num_windows: int,
                num_kernels: int, pim_read_slots: int, pim_write_slots: int,
                cpu_read_slots: int, cpu_write_slots: int) -> TraceTensors:
    """An all-sentinel trace at an exact bucket geometry: no valid access
    slots, every window invalid — each mechanism scan passes its carry
    straight through, so the lane computes (and can contribute) nothing.
    Three consumers share it: the serve layer's warm replay (same compile
    key as real traffic, near-zero work), the cross-request coalescer's
    masked pad lanes (:mod:`repro.serve.coalesce`), and the mesh planner's
    lane padding up to a device-count multiple
    (:func:`repro.sim.mesh.mesh_lane_width`).  The per-line tables are the
    real H3 positions those line ids hash to — identical to what
    ``pad_trace`` would produce — so the static spec metadata matches
    byte-for-byte."""
    n, w, k = num_lines, num_windows, num_kernels

    def slots(width):
        return jnp.full((w, width), -1, jnp.int32)

    def valid(width):
        return jnp.zeros((w, width), jnp.bool_)

    return TraceTensors(
        name="", threads=0,  # pre-neutralized: same key as neutral_trace
        num_lines=n, num_windows=w, num_kernels=k, spec=spec,
        line_pos=line_positions(spec, 0, n),
        line_reg=jnp.arange(n, dtype=jnp.int32) % CPUWS_REGS,
        pim_reads=slots(pim_read_slots),
        pim_writes=slots(pim_write_slots),
        cpu_reads=slots(cpu_read_slots),
        cpu_writes=slots(cpu_write_slots),
        pim_r_valid=valid(pim_read_slots),
        pim_w_valid=valid(pim_write_slots),
        cpu_r_valid=valid(cpu_read_slots),
        cpu_w_valid=valid(cpu_write_slots),
        kernel_id=jnp.zeros((w,), jnp.int32),
        kernel_start=jnp.zeros((w,), jnp.bool_),
        kernel_end=jnp.zeros((w,), jnp.bool_),
        pre_writes=jnp.zeros((k, n), jnp.bool_),
        pre_writes_words=jnp.zeros((k, packed_words(n)), jnp.uint32),
        pim_instr=jnp.zeros((w,), jnp.float32),
        cpu_instr=jnp.zeros((w,), jnp.float32),
        cpu_priv=jnp.zeros((w,), jnp.float32),
        cpu_priv_miss_rate=jnp.zeros((), jnp.float32),
        cpu_reuse=jnp.zeros((), jnp.float32),
        pim_uniq_r=jnp.zeros((w,), jnp.float32),
        pim_uniq_w=jnp.zeros((w,), jnp.float32),
        pim_uniq=jnp.zeros((w,), jnp.float32),
        window_valid=jnp.zeros((w,), jnp.bool_),
    )


def dummy_lane_triple(spec: SignatureSpec, shape: dict[str, int],
                      lazy_static: dict | None = None):
    """One (trace, hw, lazy) pad-lane triple at a bucket ``shape`` (the
    ``pad_trace`` kwargs): the all-sentinel :func:`dummy_trace`, default
    ``HWParams``, and a default lazy config carrying the group's static
    flags (static flags are compile-key context and must match the real
    lanes they pad).  The shared pad-lane recipe of the coalescer's
    blessed-width padding and the mesh planner's lane padding."""
    from repro.core.coherence import LazyPIMConfig

    return (dummy_trace(spec, **shape), HWParams(),
            LazyPIMConfig(**(lazy_static or {})))


# ---------------------------------------------------------------------------
# Geometry-bucketed padding (the fleet batch engine's prep layer)
# ---------------------------------------------------------------------------


def bucket_bound(n: int) -> int:
    """Pow2-ish bucket boundary: the smallest power of four >= n.

    Powers of four keep the bucket count low (the fleet's ~8 line-count
    geometries collapse to ~3 buckets) while bounding padding waste at 4x;
    plain next-pow2 rounding would leave ~6 buckets for the current fleet.
    """
    if n < 1:
        raise ValueError(f"bucket_bound needs n >= 1, got {n}")
    b = 1
    while b < n:
        b <<= 2
    return b


def pad_trace(
    tt: TraceTensors,
    *,
    num_lines: int | None = None,
    num_windows: int | None = None,
    num_kernels: int | None = None,
    pim_read_slots: int | None = None,
    pim_write_slots: int | None = None,
    cpu_read_slots: int | None = None,
    cpu_write_slots: int | None = None,
) -> TraceTensors:
    """Pad a prepared trace up to a bucket geometry, carrying explicit
    validity so padding cannot perturb any simulated quantity:

    * padded *lines* never enter a bitmap, Bloom image or CPUWriteSet bank —
      no access slot references them and every packed bitmap keeps its
      zero-pad invariant, so they are invisible to conflict detection,
      membership masks and popcounts alike;
    * padded *access slots* carry the repo-wide ``-1`` sentinel with a False
      validity mask (identical to the sentinel slots synthesis emits);
    * padded *windows* are marked invalid in ``window_valid`` — every
      mechanism step passes its scan carry through unchanged there, so they
      contribute exactly zero to every accumulator;
    * padded *kernels* have empty pre-write sets and are never referenced by
      ``kernel_id``.

    The padded rows of the per-line tables (``line_pos``/``line_reg``) are
    the real H3 hash positions / register ids those line ids would have, so
    a padded trace is indistinguishable from a trace prepared at the padded
    geometry whose extra lines are simply never touched.  Differentially
    tested bit-exact against the unpadded path on every ``SimResult`` field.
    """
    n, n2 = tt.num_lines, num_lines or tt.num_lines
    w, w2 = tt.num_windows, num_windows or tt.num_windows
    k, k2 = tt.num_kernels, num_kernels or tt.num_kernels
    widths = {
        "pim_reads": pim_read_slots, "pim_writes": pim_write_slots,
        "cpu_reads": cpu_read_slots, "cpu_writes": cpu_write_slots,
    }
    for label, cur, tgt in (("num_lines", n, n2), ("num_windows", w, w2),
                            ("num_kernels", k, k2)):
        if tgt < cur:
            raise ValueError(f"cannot shrink {label}: {cur} -> {tgt}")

    fields = {f.name: getattr(tt, f.name) for f in dataclasses.fields(tt)}
    fields.update(num_lines=n2, num_windows=w2, num_kernels=k2)

    if n2 > n:
        fields["line_pos"] = jnp.concatenate(
            [tt.line_pos, line_positions(tt.spec, n, n2)], axis=1)
        fields["line_reg"] = jnp.arange(n2, dtype=jnp.int32) % CPUWS_REGS

    valid_of = {"pim_reads": "pim_r_valid", "pim_writes": "pim_w_valid",
                "cpu_reads": "cpu_r_valid", "cpu_writes": "cpu_w_valid"}
    for key, width in widths.items():
        ids = fields[key]
        a, a2 = ids.shape[1], width or ids.shape[1]
        if a2 < a:
            raise ValueError(f"cannot shrink {key} slots: {a} -> {a2}")
        pad = ((0, w2 - w), (0, a2 - a))
        fields[key] = jnp.pad(ids, pad, constant_values=-1)
        fields[valid_of[key]] = jnp.pad(fields[valid_of[key]], pad)

    fields["kernel_id"] = jnp.pad(tt.kernel_id, (0, w2 - w))
    fields["kernel_start"] = jnp.pad(tt.kernel_start, (0, w2 - w))
    fields["kernel_end"] = jnp.pad(tt.kernel_end, (0, w2 - w))
    # Zero-padding the packed words IS packing the zero-padded boolean rows:
    # the original last word's pad bits are already zero (the invariant).
    fields["pre_writes"] = jnp.pad(tt.pre_writes, ((0, k2 - k), (0, n2 - n)))
    fields["pre_writes_words"] = jnp.pad(
        tt.pre_writes_words,
        ((0, k2 - k), (0, packed_words(n2) - packed_words(n))))
    for key in ("pim_instr", "cpu_instr", "cpu_priv",
                "pim_uniq_r", "pim_uniq_w", "pim_uniq"):
        fields[key] = jnp.pad(fields[key], (0, w2 - w))
    fields["window_valid"] = jnp.pad(tt.window_valid, (0, w2 - w))
    return TraceTensors(**fields)


def bucket_shapes(
    tts: list[TraceTensors],
) -> list[tuple[list[int], dict[str, int]]]:
    """Bucket membership and padded target shapes for a fleet — the
    grouping policy behind :func:`bucket_traces`, without materializing any
    padded trace (cheap: used by ``repro.sim.study.Study.plan`` summaries).

    The bucket key is ``(bucket_bound(num_lines), spec)`` — pow2-ish line
    rounding so near-miss geometries share one compiled scan; windows,
    kernels and access-slot widths go to the per-bucket maxima.  Returns
    ``(original_indices, pad_trace_kwargs)`` per bucket.  Deterministic for
    a fixed workload list: buckets appear in first-occurrence order and
    members keep input order, so repeated calls (and repeated runs) produce
    identical bucket shapes and compile keys.
    """
    groups: dict[tuple, list[int]] = {}
    for i, t in enumerate(tts):
        groups.setdefault((bucket_bound(t.num_lines), t.spec), []).append(i)
    out = []
    for (bound, _spec), idx in groups.items():
        member = [tts[i] for i in idx]
        out.append((idx, dict(
            num_lines=bound,
            num_windows=max(t.num_windows for t in member),
            num_kernels=max(t.num_kernels for t in member),
            pim_read_slots=max(t.pim_reads.shape[1] for t in member),
            pim_write_slots=max(t.pim_writes.shape[1] for t in member),
            cpu_read_slots=max(t.cpu_reads.shape[1] for t in member),
            cpu_write_slots=max(t.cpu_writes.shape[1] for t in member),
        )))
    return out


def bucket_traces(
    tts: list[TraceTensors],
) -> list[tuple[list[int], list[TraceTensors]]]:
    """Group prepared traces into geometry buckets (:func:`bucket_shapes`)
    and pad every member to its bucket's shape.  Returns
    ``(original_indices, padded_traces)`` per bucket."""
    return [(idx, [pad_trace(tts[i], **shape) for i in idx])
            for idx, shape in bucket_shapes(tts)]
