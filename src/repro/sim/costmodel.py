"""Timing / traffic / energy cost model for the PIM coherence simulator.

Replaces the paper's gem5 + DRAMSim2 + CACTI stack with an analytical model
whose constants mirror Table 1 and §6.3 of the paper:

* Processor: 16 cores, 8-wide issue, 2 GHz; 64 KB L1s; 2 MB shared L2; MESI.
* PIM: 16 in-order 1-wide cores @ 2 GHz in the HMC logic layer; 64 KB L1s.
* Memory: one 4 GB HMC cube (16 vaults); off-chip SerDes at 3 pJ/bit for data
  packets (the paper's interconnect energy method, from [12]/[19]).

Timing is a two-resource (latency + bandwidth) max-throughput model evaluated
per partial-kernel window; it is deliberately simple, fully vectorizable, and
calibrated (constants below) so the paper's *relative* orderings and headline
percentages are reproduced — absolute gem5 cycle counts are out of scope
(DESIGN.md §7).

Energy = cache accesses x per-access energy (CACTI-class constants, 22 nm) +
DRAM activity x pJ/bit + off-chip traffic x SerDes pJ/bit, as in §6.3.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

LINE_BYTES = 64
CTRL_BYTES = 8  # coherence request/ack packet payload

# Fields that stack as int32 in a swept HWParams axis; every other field
# stacks as float32.  This is the single explicit dtype map behind
# :func:`hw_leaf_dtypes` — sweeps that write ``offchip_bw_gbs=16`` and
# ``offchip_bw_gbs=16.0`` must land in the same compiled function, so the
# stacking dtype comes from this declaration, not from the (stringified,
# ``from __future__ import annotations``) field annotations.
_HW_INT_FIELDS = frozenset({
    "cpu_cores", "pim_cores", "cpu_cache_lines", "pim_cache_lines",
    "thread_cache_cap", "cpu_only_cache_cap", "nc_bytes",
})


def hw_leaf_dtypes() -> dict[str, jnp.dtype]:
    """Declared stacking dtype of every HWParams field (int32 counts /
    capacities, float32 everything else).  ``engine.stack_hw`` normalizes
    each swept leaf to this dtype; ``tests/test_study.py`` asserts every
    field round-trips through ``stack_hw`` at the declared dtype."""
    return {f.name: jnp.int32 if f.name in _HW_INT_FIELDS else jnp.float32
            for f in dataclasses.fields(HWParams)}

@dataclasses.dataclass(frozen=True)
class HWParams:
    """Hardware constants. Defaults model the paper's Table 1 system.

    Registered as a jit-traceable pytree with **every** field as a data
    leaf: no field determines an array shape or Python-level control flow
    (core counts, latencies, bandwidths, energies, and cache caps all enter
    the cost model arithmetically), so a single compiled simulator step
    serves every HWParams point and the batched engine
    (:func:`repro.sim.engine._sweep_fn`) can ``vmap`` one step function
    over stacked parameter axes instead of
    recompiling per sweep point (the seed passed HWParams via
    ``static_argnums``, paying one XLA compile per distinct value).
    """

    # --- compute ---
    cpu_cores: int = 16
    pim_cores: int = 16
    freq_ghz: float = 2.0
    cpu_ipc: float = 4.0   # 8-wide OoO, realistic sustained IPC on mixed code
    pim_ipc: float = 0.8   # 1-wide in-order
    # OoO memory-level parallelism: overlapped off-chip misses on the CPU.
    # Thread accesses (independent txns / bookkeeping) overlap well; the
    # kernel phase is pointer chasing — dependent loads barely overlap.
    # The in-order 1-wide PIM cores get no MLP at all (they block on every
    # miss), which is exactly why they need the low-latency TSV path.
    cpu_mlp: float = 4.0
    cpu_kernel_mlp: float = 1.8

    # --- memory timing (ns) ---
    l1_hit_ns: float = 0.5
    l2_hit_ns: float = 5.0
    # CPU off-chip DRAM access (load-to-use, incl. SerDes + DRAM + queue)
    offchip_mem_ns: float = 110.0
    # PIM access through TSVs to local vault (no SerDes, no off-chip queue)
    pim_mem_ns: float = 48.0
    # one-way off-chip control message (coherence request / ack)
    offchip_msg_ns: float = 25.0
    # FG only: exposed per-miss stall for the directory round trip (partially
    # pipelined with the vault access, so less than 2x offchip_msg_ns)
    fg_msg_exposed_ns: float = 20.0

    # --- bandwidth (GB/s) ---
    offchip_bw_gbs: float = 32.0    # usable processor<->HMC SerDes link bw
    internal_bw_gbs: float = 160.0  # aggregate TSV bandwidth inside the cube

    # --- energy (pJ) ---
    serdes_pj_per_bit: float = 3.0   # paper §6.3, data packets
    # HMC DRAM *array* access (TSV path, no SerDes/link): [19] puts the full
    # external HMC access at ~10.5 pJ/bit, of which the DRAM array + TSV part
    # is ~4; the remainder is SerDes/link/controller, charged via
    # link_pj_per_bit on off-chip transfers only.
    dram_pj_per_bit: float = 4.0
    link_pj_per_bit: float = 3.5     # off-chip path beyond SerDes (ctrl, I/O)
    l1_pj_per_access: float = 25.0   # CACTI-P 6.5, 64 KB @ 22 nm
    l2_pj_per_access: float = 120.0  # CACTI-P 6.5, 2 MB @ 22 nm
    dbi_pj_per_access: float = 10.0  # small 224 B structure (§5.7)

    # --- cache geometry (in 64 B lines) ---
    cpu_cache_lines: int = 32768     # 2 MB shared L2 (coherence point)
    pim_cache_lines: int = 1024      # 64 KB PIM L1 per core
    # Effective L2 share for processor-thread PIM-region data.  When the
    # kernel phase also runs on the CPU (CPU-only), its streaming accesses
    # thrash the shared L2, shrinking the threads' effective share.
    thread_cache_cap: int = 16384    # PIM-offload modes
    cpu_only_cache_cap: int = 4096   # CPU-only mode (kernel thrashing)
    # Non-cacheable accesses move one HMC burst (32 B min transfer), not a
    # line, and destroy row-buffer locality (each access re-activates a DRAM
    # row): their DRAM energy carries an activation overhead factor.
    nc_bytes: int = 32
    nc_dram_energy_factor: float = 3.0

    def cycles_to_ns(self, cycles: float) -> float:
        return cycles / self.freq_ghz

    # ---- timing primitives (all return ns; scalars or arrays broadcast) ----

    def compute_ns(self, instrs, cores, ipc):
        """Issue-limited execution time of `instrs` split across `cores`."""
        return instrs / (cores * ipc * self.freq_ghz)

    def offchip_transfer_ns(self, num_bytes):
        """Bandwidth-limited off-chip transfer time."""
        return num_bytes / self.offchip_bw_gbs  # bytes / (GB/s) == ns

    def internal_transfer_ns(self, num_bytes):
        return num_bytes / self.internal_bw_gbs


# Every field is a data leaf (see the class docstring), so the registration
# derives the list from the dataclass itself — one source of truth.
jax.tree_util.register_dataclass(
    HWParams,
    data_fields=tuple(f.name for f in dataclasses.fields(HWParams)),
    meta_fields=(),
)


@dataclasses.dataclass(frozen=True)
class EnergyBreakdown:
    cache_pj: float
    dram_pj: float
    offchip_pj: float

    @property
    def total_pj(self) -> float:
        return self.cache_pj + self.dram_pj + self.offchip_pj


def offchip_energy_pj(hw: HWParams, num_bytes):
    return num_bytes * 8.0 * hw.serdes_pj_per_bit


def dram_energy_pj(hw: HWParams, num_bytes):
    return num_bytes * 8.0 * hw.dram_pj_per_bit


def cache_energy_pj(hw: HWParams, l1_accesses, l2_accesses):
    return l1_accesses * hw.l1_pj_per_access + l2_accesses * hw.l2_pj_per_access
