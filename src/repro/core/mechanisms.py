"""Baseline PIM coherence mechanisms (paper §3.2, §7).

Five mechanisms share one window-granular execution model (see
``repro.sim.prep``):

* ``cpu_only``  — the whole application runs on the processor; kernel-phase
  accesses stream through the cache hierarchy with poor locality.
* ``ideal``     — PIM execution with *zero* coherence penalty (upper bound).
* ``fg``        — fine-grained MESI: every PIM L1 miss sends a request to the
  processor directory over the off-chip link; dirty lines ping-pong.
* ``cg``        — coarse-grained locks: every kernel launch flushes *all*
  dirty PIM-region lines and blocks processor accesses to the region for the
  kernel's duration.
* ``nc``        — PIM data non-cacheable in the processor: every CPU access
  to the region is an off-chip DRAM access.

The simulators run on the **packed word path** of ``repro.sim.prep``: every
per-line bitmap in the scan carry is a ``ceil(num_lines/32)`` uint32 array,
and ``HWParams`` is a traced pytree — one compiled step function serves
every hardware point (``repro.sim.engine._sweep_fn`` vmaps it over the
stacked lane axis).  The boolean seed implementations live in
``repro.core._boolref`` and are asserted bit-exact by
``tests/test_packed_engine.py``.

Each returns a :class:`SimResult` with time / traffic / energy and the
coherence-event counters the benchmarks report.  LazyPIM itself lives in
``repro.core.coherence``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.sim.costmodel import CTRL_BYTES, HWParams, LINE_BYTES
from repro.sim.prep import (
    TraceTensors,
    cpu_cache_step,
    gather_hits,
    neutral_trace,
    popcount_words,
    scatter_set,
)

__all__ = [
    "SimResult",
    "ResultIntegrityError",
    "finalize_result",
    "simulate_cpu_only",
    "simulate_ideal",
    "simulate_fg",
    "simulate_cg",
    "simulate_nc",
    "ACC_FNS",
]


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Aggregated metrics for one (trace, mechanism) simulation."""

    name: str
    mechanism: str
    time_ns: float
    offchip_bytes: float
    dram_bytes: float
    l1_accesses: float
    l2_accesses: float
    # coherence events
    commits: float = 0.0
    conflicts_sig: float = 0.0     # detected by signatures (incl. false pos.)
    conflicts_exact: float = 0.0   # ground-truth RAW conflicts
    rollbacks: float = 0.0
    flush_lines: float = 0.0
    blocked_accesses: float = 0.0
    dbi_writebacks: float = 0.0
    sig_bytes: float = 0.0

    def energy_pj(self, hw: HWParams) -> dict[str, float]:
        cache = (self.l1_accesses * hw.l1_pj_per_access
                 + self.l2_accesses * hw.l2_pj_per_access
                 + self.dbi_writebacks * hw.dbi_pj_per_access)
        dram = self.dram_bytes * 8.0 * hw.dram_pj_per_bit
        off = self.offchip_bytes * 8.0 * (hw.serdes_pj_per_bit
                                          + hw.link_pj_per_bit)
        return {"cache": cache, "dram": dram, "offchip": off,
                "total": cache + dram + off}

    @property
    def conflict_rate(self) -> float:
        return self.conflicts_sig / max(self.commits, 1.0)

    @property
    def conflict_rate_exact(self) -> float:
        return self.conflicts_exact / max(self.commits, 1.0)


def _zwords(tt: TraceTensors):
    """Empty packed line bitmap."""
    return jnp.zeros((tt.num_line_words,), dtype=jnp.uint32)


def _mask_step(tt: TraceTensors, w, old_carry, new_carry):
    """Make a window scan step padding-aware: on a window appended by
    :func:`repro.sim.prep.pad_trace` (``window_valid[w]`` False) the carry —
    accumulators included — passes through untouched, so padded windows
    contribute exactly zero to every metric.  On real windows ``where`` is a
    lane-wise select with a True predicate: bit-exact with the unmasked
    step."""
    v = tt.window_valid[w]
    return jax.tree_util.tree_map(lambda a, b: jnp.where(v, a, b),
                                  new_carry, old_carry)


def _f(x):
    return jnp.asarray(x, jnp.float32)


# ---------------------------------------------------------------------------
# Shared per-window terms
# ---------------------------------------------------------------------------


def _pim_compute_ns(tt: TraceTensors, hw: HWParams, w):
    return tt.pim_instr[w] / (hw.pim_cores * hw.pim_ipc * hw.freq_ghz)


def _pim_mem_ns(tt: TraceTensors, hw: HWParams, w, extra_per_miss=0.0):
    return tt.pim_uniq[w] * (hw.pim_mem_ns + extra_per_miss) / hw.pim_cores


def _cpu_compute_ns(tt: TraceTensors, hw: HWParams, w):
    return tt.cpu_instr[w] / (hw.cpu_cores * hw.cpu_ipc * hw.freq_ghz)


def _priv_mem_ns(tt: TraceTensors, hw: HWParams, w):
    mr = tt.cpu_priv_miss_rate
    per = mr * hw.offchip_mem_ns + (1.0 - mr) * hw.l1_hit_ns
    return tt.cpu_priv[w] * per / hw.cpu_cores


def _priv_fill_bytes(tt: TraceTensors, w):
    return tt.cpu_priv[w] * tt.cpu_priv_miss_rate * LINE_BYTES


def _pim_dram_bytes(tt: TraceTensors, w):
    """Internal (TSV) DRAM traffic of the PIM kernel itself."""
    return (tt.pim_uniq[w] + tt.pim_uniq_w[w]) * LINE_BYTES


def _cpu_acc_count(tt: TraceTensors, w):
    return (jnp.sum(tt.cpu_r_valid[w]) + jnp.sum(tt.cpu_w_valid[w])).astype(jnp.float32)


def _cpu_dyn_count(tt: TraceTensors, w):
    return _cpu_acc_count(tt, w) * tt.cpu_reuse


def _pim_acc_count(tt: TraceTensors, w):
    return (jnp.sum(tt.pim_r_valid[w]) + jnp.sum(tt.pim_w_valid[w])).astype(jnp.float32)


def _bw_bound_ns(hw: HWParams, offchip_bytes):
    return offchip_bytes / hw.offchip_bw_gbs


class ResultIntegrityError(ValueError):
    """A finalized accumulator failed the per-result integrity sentinel:
    a NaN/Inf crept into a metric, or a physically non-negative quantity
    (cycles, bytes, event counts — every ``SimResult`` field) came back
    negative.  Legitimate simulations can never produce these (every
    accumulator is a sum of non-negative float32 terms), so tripping the
    sentinel means the *execution* was corrupted — the serve layer treats
    it as a poisoned lane and quarantines the owning request rather than
    returning a wrong-but-plausible number."""


def finalize_result(name: str, mechanism: str, acc: dict) -> SimResult:
    """THE accumulator→``SimResult`` constructor: every engine (sequential
    simulators, the batched ``Study`` planner) funnels its raw
    accumulator dict through here, so result construction cannot drift
    between engines (the bit-exact cross-engine tests pin it).  Every
    value passes the NaN/Inf/negative integrity sentinel
    (:class:`ResultIntegrityError`) — per lane, since batched engines
    finalize one lane at a time."""
    vals = {k: float(v) for k, v in acc.items()}
    for k, v in vals.items():
        if not math.isfinite(v) or v < 0.0:
            raise ResultIntegrityError(
                f"integrity sentinel: {name or '<unnamed>'}/{mechanism} "
                f"{k}={v!r} (NaN/Inf/negative — corrupted execution, not a "
                f"valid simulation result)")
    return SimResult(name=name, mechanism=mechanism, **vals)


def _finalize(tt: TraceTensors, mech: str, acc: dict) -> SimResult:
    return finalize_result(tt.name, mech, acc)


# ---------------------------------------------------------------------------
# CPU-only
# ---------------------------------------------------------------------------


def _cpu_only_acc(tt: TraceTensors, hw: HWParams):
    def step(carry, w):
        present, dirty, t, off, dram, l1, l2 = carry
        k = tt.kernel_id[w]
        pre = tt.pre_writes_words[k]
        start = tt.kernel_start[w]
        present = jnp.where(start, present | pre, present)
        dirty = jnp.where(start, dirty | pre, dirty)

        out = cpu_cache_step(tt, hw, present, dirty, w,
                             cap_lines=hw.cpu_only_cache_cap)
        # Kernel phase executes on the processor: issue-limited at CPU width,
        # memory-bound accesses stream (no reuse beyond the window; the OoO
        # core overlaps the misses, but they all cross the off-chip pins).
        kern_compute = tt.pim_instr[w] / (hw.cpu_cores * hw.cpu_ipc * hw.freq_ghz)
        kern_mem = tt.pim_uniq[w] * (hw.offchip_mem_ns / hw.cpu_kernel_mlp) / hw.cpu_cores
        kern_fill = (tt.pim_uniq[w] + tt.pim_uniq_w[w]) * LINE_BYTES

        off_w = out.fill_bytes + kern_fill + _priv_fill_bytes(tt, w)
        lat = (_cpu_compute_ns(tt, hw, w) + kern_compute + kern_mem
               + out.mem_ns + _priv_mem_ns(tt, hw, w))
        t_w = jnp.maximum(lat, _bw_bound_ns(hw, off_w))

        l1_w = _cpu_dyn_count(tt, w) + _pim_acc_count(tt, w) + tt.cpu_priv[w]
        l2_w = out.misses + out.hits + tt.pim_uniq[w]
        new = (out.present, out.dirty, t + t_w, off + off_w, dram + off_w,
               l1 + l1_w, l2 + l2_w)
        return _mask_step(tt, w, carry, new), None

    init = (_zwords(tt), _zwords(tt),
            _f(0), _f(0), _f(0), _f(0), _f(0))
    (present, dirty, t, off, dram, l1, l2), _ = jax.lax.scan(
        step, init, jnp.arange(tt.num_windows))
    return dict(time_ns=t, offchip_bytes=off, dram_bytes=dram,
                l1_accesses=l1, l2_accesses=l2)


_run_cpu_only = jax.jit(_cpu_only_acc)


def simulate_cpu_only(tt: TraceTensors, hw: HWParams) -> SimResult:
    return _finalize(tt, "cpu", _run_cpu_only(neutral_trace(tt), hw))


# ---------------------------------------------------------------------------
# Ideal-PIM
# ---------------------------------------------------------------------------


def _ideal_acc(tt: TraceTensors, hw: HWParams):
    def step(carry, w):
        present, dirty, t, off, dram, l1, l2 = carry
        k = tt.kernel_id[w]
        start = tt.kernel_start[w]
        pre = tt.pre_writes_words[k]
        present = jnp.where(start, present | pre, present)
        dirty = jnp.where(start, dirty | pre, dirty)

        out = cpu_cache_step(tt, hw, present, dirty, w)
        # PIM writes update DRAM; CPU copies of those lines are refreshed for
        # free (ideal), modeled as invalidation without any message cost.
        pim_w = scatter_set(_zwords(tt), tt.pim_writes[w], tt.pim_w_valid[w],
                            tt.num_lines)
        present = out.present & ~pim_w
        dirty = out.dirty & ~pim_w

        pim_ns = _pim_compute_ns(tt, hw, w) + _pim_mem_ns(tt, hw, w)
        cpu_ns = _cpu_compute_ns(tt, hw, w) + out.mem_ns + _priv_mem_ns(tt, hw, w)
        off_w = out.fill_bytes + _priv_fill_bytes(tt, w)
        t_w = jnp.maximum(jnp.maximum(pim_ns, cpu_ns), _bw_bound_ns(hw, off_w))
        dram_w = off_w + _pim_dram_bytes(tt, w)

        l1_w = _cpu_dyn_count(tt, w) + _pim_acc_count(tt, w) + tt.cpu_priv[w]
        l2_w = out.misses + out.hits
        new = (present, dirty, t + t_w, off + off_w, dram + dram_w,
               l1 + l1_w, l2 + l2_w)
        return _mask_step(tt, w, carry, new), None

    init = (_zwords(tt), _zwords(tt),
            _f(0), _f(0), _f(0), _f(0), _f(0))
    (present, dirty, t, off, dram, l1, l2), _ = jax.lax.scan(
        step, init, jnp.arange(tt.num_windows))
    return dict(time_ns=t, offchip_bytes=off, dram_bytes=dram,
                l1_accesses=l1, l2_accesses=l2)


_run_ideal = jax.jit(_ideal_acc)


def simulate_ideal(tt: TraceTensors, hw: HWParams) -> SimResult:
    return _finalize(tt, "ideal", _run_ideal(neutral_trace(tt), hw))


# ---------------------------------------------------------------------------
# Fine-grained MESI (FG)
# ---------------------------------------------------------------------------


def _fg_acc(tt: TraceTensors, hw: HWParams):
    def step(carry, w):
        present, dirty, t, off, dram, l1, l2 = carry
        k = tt.kernel_id[w]
        start = tt.kernel_start[w]
        pre = tt.pre_writes_words[k]
        present = jnp.where(start, present | pre, present)
        dirty = jnp.where(start, dirty | pre, dirty)

        out = cpu_cache_step(tt, hw, present, dirty, w)
        present, dirty = out.present, out.dirty

        # Every PIM miss consults the processor directory over the off-chip
        # link (request + response, partially pipelined with the vault
        # access), stalling the in-order PIM pipeline.  Full MESI needs
        # request + response + invalidations + acks per transaction.
        rt_ns = hw.fg_msg_exposed_ns
        msg_bytes = tt.pim_uniq[w] * 8.0 * CTRL_BYTES

        # PIM reads/writes of CPU-dirty lines transfer the line off-chip.
        pr_dirty = gather_hits(dirty, tt.pim_reads[w], tt.pim_r_valid[w])
        pw_dirty = gather_hits(dirty, tt.pim_writes[w], tt.pim_w_valid[w])
        xfer_lines = (jnp.sum(pr_dirty) + jnp.sum(pw_dirty)).astype(jnp.float32)
        # Ownership moves to PIM: lines leave the CPU dirty set.
        dirty = dirty & ~scatter_set(_zwords(tt), tt.pim_reads[w],
                                     tt.pim_r_valid[w] & pr_dirty, tt.num_lines)
        dirty = dirty & ~scatter_set(_zwords(tt), tt.pim_writes[w],
                                     tt.pim_w_valid[w] & pw_dirty, tt.num_lines)
        # PIM exclusive writes invalidate CPU copies (next CPU access misses).
        pim_w = scatter_set(_zwords(tt), tt.pim_writes[w], tt.pim_w_valid[w],
                            tt.num_lines)
        present = present & ~pim_w

        pim_ns = (_pim_compute_ns(tt, hw, w)
                  + _pim_mem_ns(tt, hw, w, extra_per_miss=rt_ns)
                  + xfer_lines * LINE_BYTES / hw.offchip_bw_gbs)
        cpu_ns = _cpu_compute_ns(tt, hw, w) + out.mem_ns + _priv_mem_ns(tt, hw, w)
        off_w = (out.fill_bytes + _priv_fill_bytes(tt, w) + msg_bytes
                 + xfer_lines * LINE_BYTES)
        t_w = jnp.maximum(jnp.maximum(pim_ns, cpu_ns), _bw_bound_ns(hw, off_w))
        dram_w = out.fill_bytes + _priv_fill_bytes(tt, w) + _pim_dram_bytes(tt, w)

        l1_w = _cpu_dyn_count(tt, w) + _pim_acc_count(tt, w) + tt.cpu_priv[w]
        l2_w = out.misses + out.hits + tt.pim_uniq[w]  # directory lookups
        new = (present, dirty, t + t_w, off + off_w, dram + dram_w,
               l1 + l1_w, l2 + l2_w)
        return _mask_step(tt, w, carry, new), None

    init = (_zwords(tt), _zwords(tt),
            _f(0), _f(0), _f(0), _f(0), _f(0))
    (present, dirty, t, off, dram, l1, l2), _ = jax.lax.scan(
        step, init, jnp.arange(tt.num_windows))
    return dict(time_ns=t, offchip_bytes=off, dram_bytes=dram,
                l1_accesses=l1, l2_accesses=l2)


_run_fg = jax.jit(_fg_acc)


def simulate_fg(tt: TraceTensors, hw: HWParams) -> SimResult:
    return _finalize(tt, "fg", _run_fg(neutral_trace(tt), hw))


# ---------------------------------------------------------------------------
# Coarse-grained locks (CG)
# ---------------------------------------------------------------------------


def _cg_acc(tt: TraceTensors, hw: HWParams):
    def step(carry, w):
        present, dirty, t, off, dram, l1, l2, flushed, blocked = carry
        k = tt.kernel_id[w]
        start = tt.kernel_start[w]
        pre = tt.pre_writes_words[k]
        present = jnp.where(start, present | pre, present)
        dirty = jnp.where(start, dirty | pre, dirty)

        # Kernel launch: flush EVERY dirty line in the region, invalidate all.
        n_flush = jnp.where(start, popcount_words(dirty), 0).astype(jnp.float32)
        flush_bytes = n_flush * LINE_BYTES
        flush_ns = flush_bytes / hw.offchip_bw_gbs + jnp.where(start, hw.offchip_msg_ns, 0.0)
        dirty = jnp.where(start, jnp.zeros_like(dirty), dirty)
        present = jnp.where(start, jnp.zeros_like(present), present)

        # Region locked: every thread touches PIM data every window (the
        # recorded lines stand for cpu_reuse dynamic accesses spread over all
        # threads), so each in-order-committing thread stalls at its first
        # blocked access until the kernel ends.  Thread-side work therefore
        # SERIALIZES behind the kernel instead of overlapping it — this is
        # the CG pathology of §3.2 ("87.9% of accesses blocked", threads
        # "blocked up to 73.1% of total execution time").  The blocked
        # accesses then replay as misses (the region was invalidated).
        n_acc = _cpu_acc_count(tt, w)
        n_dyn = n_acc * tt.cpu_reuse
        replay_ns = (n_acc * hw.offchip_mem_ns / hw.cpu_mlp
                     + n_acc * (tt.cpu_reuse - 1.0) * hw.l2_hit_ns) / hw.cpu_cores
        deferred_fill = n_acc * LINE_BYTES

        # The replayed accesses repopulate the cache and re-dirty the
        # written lines — which the NEXT kernel launch flushes again
        # (the CG flush/refetch ping-pong of §3.2).
        present = scatter_set(present, tt.cpu_reads[w], tt.cpu_r_valid[w],
                              tt.num_lines)
        present = scatter_set(present, tt.cpu_writes[w], tt.cpu_w_valid[w],
                              tt.num_lines)
        dirty = scatter_set(dirty, tt.cpu_writes[w], tt.cpu_w_valid[w],
                            tt.num_lines)

        # A quarter of the thread compute is region-independent (private
        # data) and overlaps the kernel; the rest stalls at its first
        # blocked access and serializes behind it with the replays.
        pim_ns = _pim_compute_ns(tt, hw, w) + _pim_mem_ns(tt, hw, w)
        serial_ns = replay_ns + 0.75 * _cpu_compute_ns(tt, hw, w)
        overlap_ns = 0.25 * _cpu_compute_ns(tt, hw, w) + _priv_mem_ns(tt, hw, w)
        off_w = flush_bytes + deferred_fill + _priv_fill_bytes(tt, w)
        t_w = (jnp.maximum(jnp.maximum(pim_ns, overlap_ns) + serial_ns,
                           _bw_bound_ns(hw, off_w))
               + flush_ns)
        dram_w = off_w + _pim_dram_bytes(tt, w)

        l1_w = n_dyn + _pim_acc_count(tt, w) + tt.cpu_priv[w]
        l2_w = n_dyn + n_flush  # flush scans + replayed misses
        new = (present, dirty, t + t_w, off + off_w, dram + dram_w,
               l1 + l1_w, l2 + l2_w, flushed + n_flush, blocked + n_dyn)
        return _mask_step(tt, w, carry, new), None

    init = (_zwords(tt), _zwords(tt),
            _f(0), _f(0), _f(0), _f(0), _f(0), _f(0), _f(0))
    (present, dirty, t, off, dram, l1, l2, flushed, blocked), _ = jax.lax.scan(
        step, init, jnp.arange(tt.num_windows))
    return dict(time_ns=t, offchip_bytes=off, dram_bytes=dram,
                l1_accesses=l1, l2_accesses=l2,
                flush_lines=flushed, blocked_accesses=blocked)


_run_cg = jax.jit(_cg_acc)


def simulate_cg(tt: TraceTensors, hw: HWParams) -> SimResult:
    return _finalize(tt, "cg", _run_cg(neutral_trace(tt), hw))


# ---------------------------------------------------------------------------
# Non-cacheable PIM data (NC)
# ---------------------------------------------------------------------------


def _nc_acc(tt: TraceTensors, hw: HWParams):
    def step(carry, w):
        t, off, dram, l1, l2 = carry
        out = cpu_cache_step(tt, hw, _zwords(tt), _zwords(tt),
                             w, cacheable=False)
        pim_ns = _pim_compute_ns(tt, hw, w) + _pim_mem_ns(tt, hw, w)
        cpu_ns = _cpu_compute_ns(tt, hw, w) + out.mem_ns + _priv_mem_ns(tt, hw, w)
        off_w = out.fill_bytes + _priv_fill_bytes(tt, w)
        t_w = jnp.maximum(jnp.maximum(pim_ns, cpu_ns), _bw_bound_ns(hw, off_w))
        # every NC access is a DRAM access, and each one re-activates a row
        # (no row-buffer locality): charge the activation overhead factor.
        dram_w = (out.fill_bytes * hw.nc_dram_energy_factor
                  + _priv_fill_bytes(tt, w) + _pim_dram_bytes(tt, w))
        l1_w = _pim_acc_count(tt, w) + tt.cpu_priv[w]  # CPU accesses bypass L1
        l2_w = _f(0)
        new = (t + t_w, off + off_w, dram + dram_w, l1 + l1_w, l2 + l2_w)
        return _mask_step(tt, w, carry, new), None

    init = (_f(0), _f(0), _f(0), _f(0), _f(0))
    (t, off, dram, l1, l2), _ = jax.lax.scan(step, init, jnp.arange(tt.num_windows))
    return dict(time_ns=t, offchip_bytes=off, dram_bytes=dram,
                l1_accesses=l1, l2_accesses=l2)


_run_nc = jax.jit(_nc_acc)


def simulate_nc(tt: TraceTensors, hw: HWParams) -> SimResult:
    return _finalize(tt, "nc", _run_nc(neutral_trace(tt), hw))


# Unjitted window-scan accumulators, keyed by mechanism name — the raw
# step functions ``repro.sim.engine._sweep_fn`` vmaps over stacked
# trace/hardware axes (LazyPIM registers itself in ``repro.core.coherence``).
ACC_FNS = {
    "cpu": _cpu_only_acc,
    "ideal": _ideal_acc,
    "fg": _fg_acc,
    "cg": _cg_acc,
    "nc": _nc_acc,
}
