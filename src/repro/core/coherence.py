"""LazyPIM: speculative coherence with compressed signatures (paper §4–§5).

The protocol, per partial-kernel window:

1. The PIM kernel executes *speculatively* — no coherence messages during
   execution; reads/writes are recorded into the ``PIMReadSet`` /
   ``PIMWriteSet`` Bloom signatures (bit-exact, real H3 collisions).
2. The processor records dirty PIM-region lines at partial-kernel start plus
   its concurrent writes into the ``CPUWriteSet`` register bank (16 × 2 Kbit,
   round-robin).
3. At commit, the signatures are sent off-chip (2 × 256 B) and intersected.
   ``PIMReadSet ∩ CPUWriteSet`` non-empty in every segment ⇒ *conflict*
   (RAW): the processor flushes the dirty lines that match the PIMReadSet
   (with real signature false positives), the PIM kernel rolls back and
   re-executes.  Re-execution can conflict again on fresh concurrent writes;
   after ``max_rollbacks`` the conflicting lines are locked (forward
   progress, §5.5) and the commit succeeds.
4. On success: ``PIMWriteSet ∩ CPUWriteSet`` (WAW) lines are merged via the
   per-word dirty-bit mask — the processor's copy travels to the PIM core
   (64 B each); clean processor copies matching the PIMWriteSet are
   invalidated; speculative PIM lines drain to DRAM through the TSVs.
5. PIM-DBI (§5.6): every ``dbi_interval_cycles`` the processor opportunistically
   writes dirty PIM-region lines back to DRAM, shrinking the dominant
   *dirty conflict* class.

``partial_commits=False`` models the full-kernel-commit ablation of Fig. 12:
signatures accumulate across the whole kernel and a single conflict check
happens at kernel end (saturated filters ⇒ high false-positive rates), with
rollback replaying the entire kernel.

**Packed hot path.**  All protocol state in the scan carry is packed uint32
words (see ``repro.sim.prep``): five ``ceil(n/32)``-word line bitmaps plus
two ``sig_bits/32``-word Bloom images, instead of the seed's five ``(n,)``
and two ``(sig_bits,)`` boolean arrays.  Before the scan the step lays the
static per-line hash-position table out bit-major, ``(M, 32, words)``
(:func:`repro.sim.prep.line_pos_bit_major`), so that bit ``b`` of a packed
word sits on an axis of its own.  Per window the step looks both signature
images up over that view and gets their hits already packed, one word per
(segment, line word) (:func:`repro.sim.prep.sig_hit_words`).  Every
consumer is word algebra on those words: the membership masks are the AND
over segments (:func:`repro.sim.prep.hit_member_words`), and both conflict
checks fold the masked hit words onto the 16 register bits
(:func:`repro.sim.prep.conflict_from_hit_words`, ``bank_bits_from_bitmap``
+ ``conflict_any`` with no scatter).  The signature path makes no per-line
boolean array and no relayout between lines and words.  The boolean seed
implementation survives as :func:`repro.core._boolref.simulate_lazypim_bool`
and the differential tests assert bit-exact ``SimResult`` equality.

``LazyPIMConfig`` is a registered pytree: numeric knobs (DBI interval and
batch, commit exposure, the DBI enable) are traced data leaves, so sweeping
them reuses one compiled step; ``partial_commits`` (changes the dataflow),
``cpuws_regs`` (bank geometry) and ``max_rollbacks`` stay static metadata.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.mechanisms import (
    SimResult,
    finalize_result,
    _bw_bound_ns,
    _cpu_dyn_count,
    _cpu_compute_ns,
    _f,
    _mask_step,
    _pim_acc_count,
    _pim_compute_ns,
    _pim_dram_bytes,
    _pim_mem_ns,
    _priv_fill_bytes,
    _priv_mem_ns,
    _zwords,
)
from repro.sim.costmodel import CTRL_BYTES, HWParams, LINE_BYTES
from repro.sim.prep import (
    CPUWS_REGS,
    XXH_PRIME2,
    XXH_PRIME5,
    TraceTensors,
    conflict_from_hit_words,
    cpu_cache_step,
    hit_member_words,
    line_pos_bit_major,
    line_window_u01,
    neutral_trace as prep_neutral,
    pack_bitmap,
    popcount_words,
    scatter_set,
    sig_bits_from_ids,
    sig_hit_words,
)

__all__ = ["LazyPIMConfig", "simulate_lazypim"]


@functools.partial(
    jax.tree_util.register_dataclass,
    meta_fields=("partial_commits", "max_rollbacks", "cpuws_regs"),
    data_fields=("use_dbi", "dbi_interval_cycles", "dbi_lines_per_fire",
                 "commit_exposure"),
)
@dataclasses.dataclass(frozen=True)
class LazyPIMConfig:
    """Protocol parameters (defaults = the paper's implementation, §5).

    ``partial_commits`` selects the dataflow (fig. 12 ablation) and
    ``cpuws_regs``/``max_rollbacks`` are structural, so they are static
    metadata; the numeric knobs are traced pytree leaves — a config sweep
    reuses the single compiled step function.
    """

    partial_commits: bool = True
    use_dbi: bool = True
    # §7 uses 800 K processor cycles on full-length kernels; our traces
    # subsample kernels ~100x, so the interval compresses proportionally
    # (DESIGN.md §7).
    dbi_interval_cycles: float = 1_600.0
    max_rollbacks: int = 3                  # §5.5: lock lines after 3
    cpuws_regs: int = 16                    # §5.7
    # PIM-DBI is opportunistic (idle-bandwidth): lines written back per fire.
    dbi_lines_per_fire: int = 128
    # Fraction of the commit round (signature transfer + directory check)
    # exposed on the critical path.  Per-core commits are staggered across
    # the 16 PIM cores, so most of the latency overlaps kernel execution of
    # the other cores; the serialized directory check remains exposed.
    commit_exposure: float = 0.15


def _lazypim_acc(tt: TraceTensors, hw: HWParams, cfg: LazyPIMConfig):
    if cfg.cpuws_regs != CPUWS_REGS:
        # The fused conflict reduction groups lines by the static
        # line_reg = id % CPUWS_REGS assignment baked into the trace.
        raise NotImplementedError(
            f"cpuws_regs={cfg.cpuws_regs} != trace register assignment "
            f"({CPUWS_REGS})")
    n = tt.num_lines
    sig_bytes_per_commit = 2.0 * tt.sig_bits / 8.0  # PIMReadSet + PIMWriteSet
    dbi_interval_ns = cfg.dbi_interval_cycles / hw.freq_ghz
    pos_bm = line_pos_bit_major(tt)  # static per lane: built outside the scan

    def step(carry, w):
        carry_in = carry
        (present, dirty, cpuws, conc, read_bm, read_bits, write_bits,
         replay_ns, dbi_t, acc) = carry
        k = tt.kernel_id[w]
        start = tt.kernel_start[w]
        pre = tt.pre_writes_words[k]
        # Inter-kernel processor phase dirties lines before the kernel launch.
        present = jnp.where(start, present | pre, present)
        dirty = jnp.where(start, dirty | pre, dirty)
        dirty_before = dirty

        # --- concurrent CPU execution (fully cached under LazyPIM) ---------
        out = cpu_cache_step(tt, hw, present, dirty, w)
        present, dirty = out.present, out.dirty

        # --- signature recording -------------------------------------------
        cw_bm = scatter_set(_zwords(tt), tt.cpu_writes[w], tt.cpu_w_valid[w], n)
        fresh = cfg.partial_commits or start
        # CPUWriteSet: dirty lines scanned at (partial-)kernel start + all
        # concurrent CPU writes since.
        cpuws = jnp.where(fresh, dirty_before, cpuws) | cw_bm
        conc = jnp.where(fresh, cw_bm, conc | cw_bm)

        r_bits_w = sig_bits_from_ids(tt, tt.pim_reads[w], tt.pim_r_valid[w])
        w_bits_w = sig_bits_from_ids(tt, tt.pim_writes[w], tt.pim_w_valid[w])
        read_bits = jnp.where(fresh, r_bits_w, read_bits | r_bits_w)
        write_bits = jnp.where(fresh, w_bits_w, write_bits | w_bits_w)
        r_bm_w = scatter_set(_zwords(tt), tt.pim_reads[w], tt.pim_r_valid[w], n)
        read_bm = jnp.where(fresh, r_bm_w, read_bm | r_bm_w)

        pim_ns = _pim_compute_ns(tt, hw, w) + _pim_mem_ns(tt, hw, w)
        # Rollback replays execute against a warm PIM L1: only SPECULATIVE
        # (dirty) lines are invalidated on rollback (§5.5); clean cached
        # lines survive, so re-execution is compute-bound plus re-fetches of
        # the invalidated speculative writes and the flushed lines.
        replay_cheap = _pim_compute_ns(tt, hw, w) + (
            tt.pim_uniq_w[w] * hw.pim_mem_ns / hw.pim_cores)
        replay_ns = jnp.where(fresh, replay_cheap, replay_ns + replay_cheap)

        # --- commit / conflict detection ------------------------------------
        commit = jnp.asarray(True) if cfg.partial_commits else tt.kernel_end[w]
        # One lookup per signature image; the hit words serve both conflict
        # checks and all membership masks below.
        rhits, whits = sig_hit_words(tt, pos_bm, read_bits, write_bits)
        r_member = hit_member_words(rhits)
        w_member = hit_member_words(whits)
        c1 = conflict_from_hit_words(cpuws, rhits, cfg.cpuws_regs) & commit
        exact = jnp.any((cpuws & read_bm) != 0) & commit

        # Rollback path: flush dirty∩PIMReadSet (with FPs), replay; fresh
        # concurrent writes can conflict again; locked after max_rollbacks.
        c2 = conflict_from_hit_words(conc, rhits, cfg.cpuws_regs)
        # A second conflict during the (shorter) re-execution adds one more
        # rollback; after max_rollbacks the conflicting lines are locked and
        # the commit is guaranteed (§5.5).
        rollbacks = jnp.where(c1, 1.0 + jnp.where(c2, 1.0, 0.0), 0.0)

        c1_mask = jnp.where(c1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        flush_mask = dirty & r_member & c1_mask
        n_flush1 = popcount_words(flush_mask).astype(jnp.float32)
        n_flush_conc = popcount_words(conc & r_member).astype(jnp.float32)
        n_flush = n_flush1 + jnp.maximum(rollbacks - 1.0, 0.0) * n_flush_conc
        dirty = dirty & ~flush_mask

        flush_bytes = n_flush * LINE_BYTES
        refetch_ns = n_flush * hw.pim_mem_ns / hw.pim_cores
        rollback_ns = rollbacks * (replay_ns + refetch_ns
                                   + 2.0 * hw.offchip_msg_ns
                                   + sig_bytes_per_commit / hw.offchip_bw_gbs)
        rollback_ns = rollback_ns + flush_bytes / hw.offchip_bw_gbs

        # Successful commit: WAW merge + clean-line invalidation + drain.
        commit_mask = jnp.where(commit, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        merge_mask = dirty & w_member & commit_mask
        n_merge = popcount_words(merge_mask).astype(jnp.float32)
        inv_mask = present & w_member & commit_mask
        present = present & ~inv_mask
        dirty = dirty & ~merge_mask

        attempts = jnp.where(commit, 1.0 + rollbacks, 0.0)
        commit_bytes = (attempts * (sig_bytes_per_commit + 2.0 * CTRL_BYTES)
                        + n_merge * LINE_BYTES)
        commit_ns = jnp.where(
            commit,
            cfg.commit_exposure * (2.0 * hw.offchip_msg_ns
                                   + sig_bytes_per_commit / hw.offchip_bw_gbs),
            0.0)

        # --- window timing ---------------------------------------------------
        cpu_ns = _cpu_compute_ns(tt, hw, w) + out.mem_ns + _priv_mem_ns(tt, hw, w)
        off_w = (out.fill_bytes + _priv_fill_bytes(tt, w) + commit_bytes
                 + flush_bytes)
        t_w = (jnp.maximum(jnp.maximum(pim_ns, cpu_ns), _bw_bound_ns(hw, off_w))
               + commit_ns + rollback_ns)
        dram_w = (out.fill_bytes + _priv_fill_bytes(tt, w) + _pim_dram_bytes(tt, w)
                  + flush_bytes + n_merge * LINE_BYTES)

        # --- PIM-DBI (§5.6): opportunistic dirty writeback -------------------
        # The DBI drains dirty PIM-region lines during idle-bandwidth
        # periods, so each fire writes back a bounded batch.
        dbi_t = dbi_t + t_w
        fire = jnp.asarray(cfg.use_dbi) & (dbi_t > dbi_interval_ns)
        n_dirty = popcount_words(dirty).astype(jnp.float32)
        frac = jnp.clip(cfg.dbi_lines_per_fire / jnp.maximum(n_dirty, 1.0), 0.0, 1.0)
        u = line_window_u01(n, w, XXH_PRIME2, XXH_PRIME5)
        fire_mask = jnp.where(fire, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        drain = dirty & pack_bitmap(u < frac) & fire_mask
        n_dbi = popcount_words(drain).astype(jnp.float32)
        dirty = dirty & ~drain
        dbi_t = jnp.where(fire, 0.0, dbi_t)
        off_w = off_w + n_dbi * LINE_BYTES
        dram_w = dram_w + n_dbi * LINE_BYTES

        # --- accumulate -------------------------------------------------------
        l1_w = _cpu_dyn_count(tt, w) + _pim_acc_count(tt, w) + tt.cpu_priv[w]
        l2_w = out.misses + out.hits + n_flush + n_dbi
        acc = dict(
            time_ns=acc["time_ns"] + t_w,
            offchip_bytes=acc["offchip_bytes"] + off_w,
            dram_bytes=acc["dram_bytes"] + dram_w,
            l1_accesses=acc["l1_accesses"] + l1_w,
            l2_accesses=acc["l2_accesses"] + l2_w,
            commits=acc["commits"] + jnp.where(commit, 1.0, 0.0),
            conflicts_sig=acc["conflicts_sig"] + jnp.where(c1, 1.0, 0.0),
            conflicts_exact=acc["conflicts_exact"] + jnp.where(exact, 1.0, 0.0),
            rollbacks=acc["rollbacks"] + rollbacks,
            flush_lines=acc["flush_lines"] + n_flush,
            dbi_writebacks=acc["dbi_writebacks"] + n_dbi,
            sig_bytes=acc["sig_bytes"] + attempts * sig_bytes_per_commit,
        )
        # Reset per-commit state after a successful commit.
        zero_bits = jnp.zeros_like(read_bits)
        read_bits = jnp.where(commit, zero_bits, read_bits)
        write_bits = jnp.where(commit, zero_bits, write_bits)
        read_bm = jnp.where(commit, jnp.zeros_like(read_bm), read_bm)
        conc = jnp.where(commit, jnp.zeros_like(conc), conc)
        cpuws = jnp.where(commit, jnp.zeros_like(cpuws), cpuws)
        replay_ns = jnp.where(commit, 0.0, replay_ns)

        new = (present, dirty, cpuws, conc, read_bm, read_bits, write_bits,
               replay_ns, dbi_t, acc)
        return _mask_step(tt, w, carry_in, new), None

    acc0 = {k: _f(0) for k in (
        "time_ns", "offchip_bytes", "dram_bytes", "l1_accesses", "l2_accesses",
        "commits", "conflicts_sig", "conflicts_exact", "rollbacks",
        "flush_lines", "dbi_writebacks", "sig_bytes")}
    sig_zero = jnp.zeros((tt.sig_words,), jnp.uint32)
    init = (_zwords(tt), _zwords(tt), _zwords(tt), _zwords(tt), _zwords(tt),
            sig_zero, sig_zero,
            _f(0), _f(0), acc0)
    final, _ = jax.lax.scan(step, init, jnp.arange(tt.num_windows))
    return final[-1]


_run_lazypim = jax.jit(_lazypim_acc)


def simulate_lazypim(
    tt: TraceTensors, hw: HWParams, cfg: LazyPIMConfig | None = None
) -> SimResult:
    cfg = cfg or LazyPIMConfig()
    acc = _run_lazypim(prep_neutral(tt), hw, cfg)
    return finalize_result(tt.name, "lazypim", acc)
