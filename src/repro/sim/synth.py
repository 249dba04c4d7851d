"""JAX-native trace synthesis core (tentpole of ISSUE 3).

The seed repo generated window traces with sequential numpy loops
(``np.random.default_rng`` drawn window by window), which PR 2's
``fig7_end_to_end`` measurement showed now rivals the packed simulation
itself in wall-clock.  This module rewrites synthesis as a *counter-based*
generator: every random value is a pure function of a (key, counter) pair
hashed through Threefry-2x32 — the same counter-based construction behind
``jax.random`` — so the whole trace is one embarrassingly-parallel tensor
program that jit-compiles and runs on-device.  Generation never leaves the
device, which is what makes ≥1M-line instances feasible.

**Differential discipline.**  The per-element math (Threefry rounds, draw
helpers, line-layout arithmetic, instruction-count formulas) is written
once, parameterized over the array namespace (``numpy`` or ``jax.numpy``),
and shared with the sequential numpy reference in
:mod:`repro.sim._traceref` — the same discipline ``core/_boolref.py``
established for the simulator.  ``tests/test_trace_synth.py`` asserts the
JAX path regenerates every reference workload bit-identically (same seeds,
same arrays, every ``WindowTrace`` field).

**Key derivation.**  The seed repo's ``zlib.crc32``-based seed mixing was
duplicated between the graph and HTAP constructors; it is hoisted here into
one audited :func:`derive_key` / :func:`derive_keys` helper shared by the
numpy and JAX paths, so the two can never silently diverge.  Each logical
random stream (edge-window starts, bookkeeping vertices, concurrent-write
coins, ...) gets its own Threefry key; counters index the draw within the
stream (window × slot), never sequential state.

Static *plan* dataclasses (:class:`GraphPlan` & co.) hold everything known
at trace-construction time — layout bases, per-kernel window sizes,
slot counts — computed host-side in plain Python so float-precision
subtleties (e.g. ``int(E * frac ** k)``) can never differ between paths.
Plans are hashable and serve as the jit static argument; Threefry keys are
*traced* ``uint32`` tensors, so regenerating at a different seed reuses the
compiled generator.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.sim import graphs as G

# Window geometry: a partial kernel ends at 250 inserted addresses (§5.4).
MAX_SIG_ADDRS = 250
AR = 256  # PIM read slots per window
AW = 256  # PIM write slots per window
BR = 64   # CPU->PIM-region read slots per window
BW = 64   # CPU->PIM-region write slots per window

VPL = 64 // G.VERTEX_VALUE_BYTES  # vertices per line
EPL = 64 // G.EDGE_BYTES          # edges per line


# ---------------------------------------------------------------------------
# Counter-based PRNG core (Threefry-2x32), shared numpy/jnp
# ---------------------------------------------------------------------------

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)


def threefry2x32(xp, k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds — the counter-based block cipher behind
    ``jax.random``.  ``k0``/``k1`` are uint32 key scalars (may be traced),
    ``c0``/``c1`` uint32 counter arrays.  Identical bit-for-bit under
    ``xp = numpy`` and ``xp = jax.numpy`` (differentially tested)."""
    k0 = xp.asarray(k0, xp.uint32)
    k1 = xp.asarray(k1, xp.uint32)
    ks2 = xp.asarray(np.uint32(0x1BD11BDA), xp.uint32) ^ k0 ^ k1
    x0 = xp.asarray(c0, xp.uint32) + k0
    x1 = xp.asarray(c1, xp.uint32) + k1
    ks = (k0, k1, ks2)
    for d in range(5):
        for r in _ROT_A if d % 2 == 0 else _ROT_B:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(d + 1) % 3]
        x1 = x1 + ks[(d + 2) % 3] + xp.asarray(np.uint32(d + 1), xp.uint32)
    return x0, x1


def counter_bits(xp, key, ctr):
    """uint32 random bits for each counter in ``ctr`` under stream ``key``."""
    ctr = xp.asarray(ctr, xp.uint32)
    x0, _ = threefry2x32(xp, key[0], key[1], ctr, xp.zeros_like(ctr))
    return x0


def counter_u01(xp, key, ctr):
    """float32 uniform in [0, 1) — top 24 bits scaled (exactly representable,
    so numpy and jnp agree to the last bit)."""
    return (counter_bits(xp, key, ctr) >> np.uint32(8)).astype(xp.float32) \
        * np.float32(2.0 ** -24)


def counter_mod(xp, key, ctr, bound):
    """int32 uniform in [0, bound) via modulo (bias < bound / 2**32 —
    negligible for synthesis; identical in both namespaces)."""
    b = xp.asarray(bound, xp.uint32)
    return (counter_bits(xp, key, ctr) % b).astype(xp.int32)


def derive_key(app: str, graph_name: str | None, seed: int, stream: str):
    """The single audited seed-mixing rule (hoisted from the seed repo's
    duplicated ``trace.py`` key-salt blocks): stream key0 is the CRC-32 of
    the workload/stream label, key1 a Weyl-mixed seed.  Both the numpy and
    JAX generators consume keys from here and only here."""
    label = f"{app}/{graph_name or ''}/{stream}"
    k0 = np.uint32(zlib.crc32(label.encode()) & 0xFFFFFFFF)
    k1 = np.uint32((seed * 2654435761 + 0x9E3779B9) & 0xFFFFFFFF)
    return k0, k1


def derive_keys(app: str, graph_name: str | None, seed: int,
                streams: tuple[str, ...]) -> np.ndarray:
    """(S, 2) uint32 key table, one row per named stream (fixed order)."""
    return np.stack([np.asarray(derive_key(app, graph_name, seed, s))
                     for s in streams]).astype(np.uint32)


# ---------------------------------------------------------------------------
# Shared per-family arithmetic (line layout + instruction-count formulas)
# ---------------------------------------------------------------------------


def vline(base: int, v):
    """Vertex-array cache line (8 values per 64 B line)."""
    return np.int32(base) + v // VPL


def fline(base: int, v):
    """Frontier bitmap cache line (1 B per flag)."""
    return np.int32(base) + v // 64


def eline(base: int, e):
    """CSR edge-array cache line (8 edges per line)."""
    return np.int32(base) + e // EPL


def tline(plan, table, tup, fld):
    """Tuple-field cache line of a (table, tuple, field) triple in an IMDB
    layout plan (HTAP families)."""
    return ((table * plan.tuples + tup) * plan.tuple_lines + fld).astype(np.int32)


def gtline(plan, gidx, fld):
    """Tuple-field cache line of a *global* tuple index in the append-ring
    (streaming family; tables are contiguous, so the ring is linear)."""
    return (gidx * plan.tuple_lines + fld).astype(np.int32)


def instr_counts(xp, plan, n_pim_acc, n_cpu_acc):
    """(pim_instr, cpu_instr, cpu_priv) float32 — one shared float32
    expression so the two paths cannot round differently."""
    pim = n_pim_acc.astype(xp.float32) * np.float32(plan.pim_ipw)
    cpu = (n_cpu_acc.astype(xp.float32) * np.float32(plan.cpu_reuse)
           * np.float32(plan.cpu_ipw)
           + np.float32(plan.threads * plan.cpu_serial_instr))
    priv = xp.full(n_pim_acc.shape, np.float32(plan.threads * plan.priv_apw),
                   xp.float32)
    return pim, cpu, priv


# ---------------------------------------------------------------------------
# Plans: static, hashable geometry computed host-side
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphPlan:
    """Seed graph family (Ligra edgeMap: pagerank / radii / components)."""

    app: str
    graph_name: str
    threads: int
    num_kernels: int
    wpk: int
    n: int                     # nodes
    E: int                     # edges
    p_next_base: int
    frontier_base: int
    edge_base: int
    total_lines: int
    hi: tuple[int, ...]        # per-kernel e0 bound (host-computed)
    epw: int                   # edges per window
    raw_int: int               # guaranteed RAW-capable writes per window
    raw_frac: float            # probability of one extra RAW write
    raw_max: int
    hot_bias: float
    writes_src: bool           # pagerank writes p_next[src]; others [dst]
    pool_n: int = 600
    reads_n: int = 44
    bk_n: int = 4
    cpu_reuse: float = 6.0
    pim_ipw: float = 3.0
    cpu_ipw: float = 6.0
    cpu_serial_instr: float = 420.0
    priv_apw: float = 160.0
    cpu_priv_miss_rate: float = 0.002

    STREAMS = ("e0", "bk", "pool", "rawn", "rawhot", "rawhotv", "rawuni",
               "safe", "crs")

    @property
    def num_windows(self) -> int:
        return self.num_kernels * self.wpk


@dataclasses.dataclass(frozen=True)
class FrontierPlan:
    """BFS/SSSP frontier family: bursty frontier-sized windows."""

    app: str
    graph_name: str
    threads: int
    num_kernels: int
    wpk: int
    n: int
    E: int
    p_next_base: int
    frontier_base: int
    edge_base: int
    total_lines: int
    epw: tuple[int, ...]       # per-kernel (level) edges per window — bursty
    epw_max: int
    relax_rate: float          # fraction of edges producing a dist write
    qraw_rate: float           # host-side relaxation (RAW) writes per window
    pool_n: int = 600
    reads_n: int = 36
    bk_n: int = 6
    cpu_reuse: float = 6.0
    pim_ipw: float = 2.5
    cpu_ipw: float = 6.0
    cpu_serial_instr: float = 380.0
    priv_apw: float = 150.0
    cpu_priv_miss_rate: float = 0.002

    STREAMS = ("f0", "relax", "qsafe", "qraw", "qrawv", "pool", "crs", "bk")

    @property
    def num_windows(self) -> int:
        return self.num_kernels * self.wpk


@dataclasses.dataclass(frozen=True)
class HtapPlan:
    """Seed HTAP family (analytics on PIM, transactions on CPU)."""

    app: str
    threads: int
    num_kernels: int
    wpk: int
    tables: int
    tuples: int                # tuples per table (scaled)
    tuple_lines: int
    hash_base: int
    hash_lines: int
    total_lines: int
    n_scan: int
    n_probe: int
    n_wr: int                  # join build/output writes (intensity-scaled)
    intensity: float
    txn_writes: int = 2
    txn_hot: int = 1           # txn writes biased into the scanned table
    txn_reads: int = 26
    burst_n: int = 8
    burst_hot: int = 3
    pool_n: int = 500
    cpu_reuse: float = 6.0
    cpu_ipw: float = 12.0
    cpu_serial_instr: float = 500.0
    priv_apw: float = 220.0
    cpu_priv_miss_rate: float = 0.0015

    STREAMS = ("tbl", "cur", "btab", "btup", "bfld", "probe", "wrh",
               "twtab", "twtup", "twfld", "ptab", "ptup", "pfld", "txr")

    @property
    def pim_ipw(self) -> float:
        return 2.5 + 1.5 * self.intensity

    @property
    def num_windows(self) -> int:
        return self.num_kernels * self.wpk


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Streaming-ingest HTAP: append-heavy transactions at a moving tail,
    analytics scanning the recently-ingested region (real-time analytics —
    the LazyPIM target case; hot-tail RAW + dirty-conflict pressure)."""

    app: str
    threads: int
    num_kernels: int
    wpk: int
    tables: int
    tuples: int
    tuple_lines: int
    hash_base: int
    hash_lines: int
    total_lines: int
    total_tuples: int          # ring size (tables * tuples)
    apw: int = 6               # appended tuples per window (the hot tail)
    lag: int = 96              # analytics scans tuples appended `lag` ago
    n_scan: int = 40
    n_probe: int = 10
    n_wr: int = 24
    idx_writes: int = 2        # txn index-maintenance writes (hash area)
    txn_reads: int = 24
    recent: int = 512          # hot read window behind the tail (reuse-heavy)
    burst_n: int = 8
    cpu_reuse: float = 8.0
    pim_ipw: float = 4.0
    cpu_ipw: float = 12.0
    cpu_serial_instr: float = 500.0
    priv_apw: float = 220.0
    cpu_priv_miss_rate: float = 0.0015

    STREAMS = ("probe", "wrh", "idxw", "txr", "burst")

    @property
    def num_windows(self) -> int:
        return self.num_kernels * self.wpk


@dataclasses.dataclass(frozen=True)
class MTPlan:
    """Multi-tenant mix: two applications' kernels interleave over one
    shared PIM data region (shared CSR edges, private vertex arrays) —
    cross-kernel CPUWriteSet pressure (§5.6): while tenant A's kernel runs,
    tenant B's processor threads keep dirtying B's region, filling the
    CPUWriteSet bank and aliasing into A's PIMReadSet via real H3 false
    positives."""

    app: str
    graph_name: str
    threads: int
    num_kernels: int
    wpk: int
    n: int
    E: int
    # tenant A (pagerank-like) bases
    a_pc: int
    a_pn: int
    a_fr: int
    # tenant B (label-propagation-like) bases
    b_pc: int
    b_pn: int
    b_fr: int
    edge_base: int
    total_lines: int
    hi_a: tuple[int, ...]      # per-A-kernel e0 bounds
    hi_b: tuple[int, ...]
    epw: int = 60
    a_raw_frac: float = 0.5    # A: 0/1 uniform RAW writes per window
    b_raw_int: int = 0         # B: 0/1 hot RAW writes per window
    b_raw_frac: float = 0.7
    b_hot_bias: float = 0.5
    pool_n: int = 600
    reads_n: int = 40          # 20 per tenant
    bk_n: int = 4
    cpu_reuse: float = 6.0
    pim_ipw: float = 3.0
    cpu_ipw: float = 6.0
    cpu_serial_instr: float = 460.0
    priv_apw: float = 200.0
    cpu_priv_miss_rate: float = 0.002

    STREAMS = ("e0A", "e0B", "bkA", "bkB", "poolA", "poolB", "rawnA",
               "rawuniA", "safeA", "rawnB", "rawhotB", "rawhotvB", "rawuniB",
               "safeB", "crsA", "crsB")

    @property
    def num_windows(self) -> int:
        return self.num_kernels * self.wpk


# Per-app concurrent-write behavior of the seed graph family:
# (raw_write_rate per window, hot_bias) — rates < 1 mean a RAW-capable write
# happens only in that fraction of windows.
APP_CPU_WRITES = {
    "pagerank": (0.35, 0.0),    # regular sweep, uniform bookkeeping
    "radii": (0.6, 0.35),       # frontier-based, medium overlap
    "components": (1.5, 0.85),  # label propagation on hot vertices (worst)
}

FRONTIER_PARAMS = {
    # (peak edges/window, level-peak position, level width, relax, qraw)
    "bfs": (110, 0.30, 0.20, 0.45, 0.25),
    "sssp": (90, 0.38, 0.33, 0.70, 0.90),
}


def build_graph_plan(app, graph_name, threads=16, num_kernels=24, wpk=3,
                     seed=0, scale=1.0, cpu_reuse=6.0, **graph_kw):
    g = G.make_graph(graph_name, seed=seed, scale=scale, **graph_kw)
    lay = G.layout_for_graph(g)
    raw_w, hot_bias = APP_CPU_WRITES[app]
    frontier_frac = {"pagerank": 1.0, "radii": 0.45, "components": 0.6}[app]
    hi = tuple(
        max(1, g.num_edges - max(64, int(g.num_edges * frontier_frac ** (k % 6))))
        for k in range(num_kernels))
    raw_int = int(raw_w)
    raw_frac = raw_w - raw_int
    plan = GraphPlan(
        app=app, graph_name=graph_name, threads=threads,
        num_kernels=num_kernels, wpk=wpk, n=g.num_nodes, E=g.num_edges,
        p_next_base=lay.p_next_base, frontier_base=lay.frontier_base,
        edge_base=lay.edge_base, total_lines=lay.total_lines,
        hi=hi, epw=60, raw_int=raw_int, raw_frac=raw_frac,
        raw_max=raw_int + (1 if raw_frac > 0 else 0), hot_bias=hot_bias,
        writes_src=(app == "pagerank"), cpu_reuse=cpu_reuse)
    return plan, g.edges


def build_frontier_plan(app, graph_name, threads=16, num_kernels=24, wpk=3,
                        seed=0, scale=1.0, cpu_reuse=6.0, **graph_kw):
    import math

    g = G.make_graph(graph_name, seed=seed, scale=scale, **graph_kw)
    lay = G.layout_for_graph(g)
    peak_epw, peak_pos, width, relax, qraw = FRONTIER_PARAMS[app]
    # BFS-level bell: tiny frontiers at the root and the fringe, a burst of
    # frontier-sized windows around the peak level (host-computed, static).
    epw = tuple(
        max(6, int(peak_epw * math.exp(
            -0.5 * ((k - peak_pos * num_kernels) / (width * num_kernels)) ** 2)))
        for k in range(num_kernels))
    plan = FrontierPlan(
        app=app, graph_name=graph_name, threads=threads,
        num_kernels=num_kernels, wpk=wpk, n=g.num_nodes, E=g.num_edges,
        p_next_base=lay.p_next_base, frontier_base=lay.frontier_base,
        edge_base=lay.edge_base, total_lines=lay.total_lines,
        epw=epw, epw_max=max(epw), relax_rate=relax, qraw_rate=qraw,
        cpu_reuse=cpu_reuse)
    return plan, g.edges


def build_htap_plan(app, threads=16, num_kernels=24, wpk=3, seed=0,
                    scale=0.01, cpu_reuse=6.0):
    n_queries = int(app.replace("htap", ""))
    lay = G.make_imdb_layout(scale=scale)
    tuples = int(G.IMDB_SHAPE["tuples_per_table"] * scale)
    # tline's linear algebra assumes tables are packed back-to-back
    assert lay.table_lines == tuples * lay.tuple_lines
    intensity = n_queries / 128.0
    return HtapPlan(
        app=app, threads=threads, num_kernels=num_kernels, wpk=wpk,
        tables=lay.tables, tuples=tuples, tuple_lines=lay.tuple_lines,
        hash_base=lay.hash_base, hash_lines=lay.hash_area_lines,
        total_lines=lay.total_lines, n_scan=35, n_probe=12,
        n_wr=max(8, int(40 * intensity)), intensity=intensity,
        cpu_reuse=cpu_reuse)


def build_stream_plan(app="htap_stream", threads=16, num_kernels=24, wpk=3,
                      seed=0, scale=0.01, cpu_reuse=8.0):
    lay = G.make_imdb_layout(scale=scale)
    tuples = int(G.IMDB_SHAPE["tuples_per_table"] * scale)
    # gtline's ring is linear only while tables are packed back-to-back
    assert lay.table_lines == tuples * lay.tuple_lines
    return StreamPlan(
        app=app, threads=threads, num_kernels=num_kernels, wpk=wpk,
        tables=lay.tables, tuples=tuples, tuple_lines=lay.tuple_lines,
        hash_base=lay.hash_base, hash_lines=lay.hash_area_lines,
        total_lines=lay.total_lines, total_tuples=lay.tables * tuples,
        cpu_reuse=cpu_reuse)


def build_mt_plan(app, graph_name, threads=16, num_kernels=24, wpk=3,
                  seed=0, scale=1.0, cpu_reuse=6.0, **graph_kw):
    if num_kernels < 2:
        # tenant B would get zero kernels — the vectorized generator's
        # tenant-select gathers need at least one kernel per tenant
        raise ValueError(f"mtmix interleaves two tenants: num_kernels must "
                         f"be >= 2, got {num_kernels}")
    g = G.make_graph(graph_name, seed=seed, scale=scale, **graph_kw)
    lay = G.mt_layout_for_graph(g)
    ka = (num_kernels + 1) // 2   # tenant A runs even kernels
    kb = num_kernels // 2
    hi_a = tuple(1 for _ in range(ka))  # pagerank-like: full sweep
    hi_b = tuple(
        max(1, g.num_edges - max(64, int(g.num_edges * 0.6 ** (k % 6))))
        for k in range(kb))
    plan = MTPlan(
        app=app, graph_name=graph_name, threads=threads,
        num_kernels=num_kernels, wpk=wpk, n=g.num_nodes, E=g.num_edges,
        a_pc=lay.a_pc, a_pn=lay.a_pn, a_fr=lay.a_fr,
        b_pc=lay.b_pc, b_pn=lay.b_pn, b_fr=lay.b_fr,
        edge_base=lay.edge_base, total_lines=lay.total_lines,
        hi_a=hi_a, hi_b=hi_b, cpu_reuse=cpu_reuse)
    return plan, g.edges


# ---------------------------------------------------------------------------
# Vectorized JAX generators (one jit-compiled tensor program per plan)
# ---------------------------------------------------------------------------


def _kernel_structure(xp, plan):
    K, wpk = plan.num_kernels, plan.wpk
    kid = xp.repeat(xp.arange(K, dtype=xp.int32), wpk)
    j = xp.arange(K * wpk, dtype=xp.int32) % wpk
    return kid, j, j == 0, j == wpk - 1


def _pad_cols(xp, arr, width):
    """Pad (W, S) id columns with the -1 sentinel out to (W, width)."""
    return xp.concatenate(
        [arr.astype(xp.int32),
         xp.full((arr.shape[0], width - arr.shape[1]), -1, xp.int32)], axis=1)


def _acc_counts(xp, *arrs):
    n = None
    for a in arrs:
        c = xp.sum(a >= 0, axis=1).astype(xp.int32)
        n = c if n is None else n + c
    return n



def _finish_arrays(xp, plan, reads, writes, cwr, crd, kid, start, end, pre):
    """Shared finishing block of every vectorized generator: pad the slot
    columns to the fixed window geometry, derive the instruction counts,
    and assemble the WindowTrace field dict (the JAX twin of
    ``_traceref._finish`` — one edit point for the bit-identity contract)."""
    pim_reads = _pad_cols(xp, reads, AR)
    pim_writes = _pad_cols(xp, writes, AW)
    cpu_writes = _pad_cols(xp, cwr, BW)
    cpu_reads = _pad_cols(xp, crd, BR)
    pim_i, cpu_i, priv = instr_counts(
        xp, plan, _acc_counts(xp, pim_reads, pim_writes),
        _acc_counts(xp, cpu_reads, cpu_writes))
    return dict(pim_reads=pim_reads, pim_writes=pim_writes,
                cpu_reads=cpu_reads, cpu_writes=cpu_writes, kernel_id=kid,
                kernel_start=start, kernel_end=end, pre_writes=pre,
                pim_instr=pim_i, cpu_instr=cpu_i, cpu_priv_accesses=priv)


def _graph_arrays(plan: GraphPlan, keys, edges):
    """All WindowTrace tensors for the seed graph family, fully vectorized."""
    xp = jnp
    key = dict(zip(GraphPlan.STREAMS, keys))
    W, K, epw = plan.num_windows, plan.num_kernels, plan.epw
    kid, j, start, end = _kernel_structure(xp, plan)

    # kernel structure: per-kernel edge-window origin + bookkeeping vertices
    e0 = counter_mod(xp, key["e0"], xp.arange(K, dtype=xp.uint32),
                     np.asarray(plan.hi, np.uint32))
    bk = counter_mod(xp, key["bk"],
                     xp.arange(K * plan.bk_n, dtype=xp.uint32),
                     plan.n).reshape(K, plan.bk_n)
    pre_lines = xp.concatenate([fline(plan.frontier_base, bk), vline(0, bk)], 1)
    pre = xp.zeros((K, plan.total_lines), bool)
    pre = pre.at[xp.arange(K, dtype=xp.int32)[:, None], pre_lines].set(True)

    # edgeMap windows: sequential edge lines + scattered p_curr gathers
    lo = e0[kid] + j * epw                                   # (W,)
    eidx = (lo[:, None] + xp.arange(epw, dtype=xp.int32)) % plan.E
    src = edges[eidx, 0]
    dst = edges[eidx, 1]
    reads = xp.zeros((W, 2 * epw), xp.int32)
    reads = reads.at[:, 0::2].set(eline(plan.edge_base, eidx))
    reads = reads.at[:, 1::2].set(vline(0, dst))
    writes = vline(plan.p_next_base, src if plan.writes_src else dst)

    # concurrent processor threads: RAW-capable p_curr writes + 1 safe write
    R = plan.raw_max
    rctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(R)
            + xp.arange(R, dtype=xp.uint32))
    coin = counter_u01(xp, key["rawn"], xp.arange(W, dtype=xp.uint32)) \
        < np.float32(plan.raw_frac)
    rvalid = (xp.arange(R, dtype=xp.int32) < plan.raw_int) | \
        ((xp.arange(R, dtype=xp.int32) == plan.raw_int) & coin[:, None])
    hot = counter_u01(xp, key["rawhot"], rctr) < np.float32(plan.hot_bias)
    v_hot = edges[counter_mod(xp, key["rawhotv"], rctr, plan.E), 1]
    v_uni = counter_mod(xp, key["rawuni"], rctr, plan.n)
    raw_lines = xp.where(rvalid, vline(0, xp.where(hot, v_hot, v_uni)), -1)
    safe_v = counter_mod(xp, key["safe"], xp.arange(W, dtype=xp.uint32), plan.n)
    cwr = xp.concatenate([raw_lines, vline(plan.p_next_base, safe_v)[:, None]], 1)

    # cached bookkeeping reads from a stable hot-vertex pool
    pool = counter_mod(xp, key["pool"],
                       xp.arange(plan.pool_n, dtype=xp.uint32), plan.n)
    cctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.reads_n)
            + xp.arange(plan.reads_n, dtype=xp.uint32))
    cv = pool[counter_mod(xp, key["crs"], cctr, plan.pool_n)]
    half = plan.reads_n // 2
    crd = xp.concatenate([vline(plan.p_next_base, cv[:, :half]),
                          fline(plan.frontier_base, cv[:, half:])], 1)

    return _finish_arrays(xp, plan, reads, writes, cwr, crd, kid, start, end, pre)


def _frontier_arrays(plan: FrontierPlan, keys, edges):
    """BFS/SSSP frontier kernels: bursty, frontier-sized windows."""
    xp = jnp
    key = dict(zip(FrontierPlan.STREAMS, keys))
    W, K, S = plan.num_windows, plan.num_kernels, plan.epw_max
    kid, j, start, end = _kernel_structure(xp, plan)
    epw = np.asarray(plan.epw, np.int32)

    f0 = counter_mod(xp, key["f0"], xp.arange(K, dtype=xp.uint32), plan.E)
    bk = counter_mod(xp, key["bk"],
                     xp.arange(K * plan.bk_n, dtype=xp.uint32),
                     plan.n).reshape(K, plan.bk_n)
    pre_lines = xp.concatenate([fline(plan.frontier_base, bk), vline(0, bk)], 1)
    pre = xp.zeros((K, plan.total_lines), bool)
    pre = pre.at[xp.arange(K, dtype=xp.int32)[:, None], pre_lines].set(True)

    # frontier edge sweep, level-sized: slots past this level's frontier are
    # empty (-1 in place) — the windows themselves are bursty.
    epw_w = xp.asarray(epw)[kid]                              # (W,)
    slot = xp.arange(S, dtype=xp.int32)
    alive = slot[None, :] < epw_w[:, None]                    # (W, S)
    lo = f0[kid] + j * epw_w
    eidx = (lo[:, None] + slot[None, :]) % plan.E
    dst = edges[eidx, 1]
    reads = xp.zeros((W, 2 * S), xp.int32)
    reads = reads.at[:, 0::2].set(xp.where(alive, eline(plan.edge_base, eidx), -1))
    reads = reads.at[:, 1::2].set(xp.where(alive, vline(0, dst), -1))
    relax_ctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(S)
                 + xp.arange(S, dtype=xp.uint32))
    relaxed = counter_u01(xp, key["relax"], relax_ctr) < np.float32(plan.relax_rate)
    writes = xp.where(alive & relaxed, vline(plan.p_next_base, dst), -1)

    # host threads: frontier-queue writes (safe) + occasional dist
    # relaxation assists (RAW-capable)
    qctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(2)
            + xp.arange(2, dtype=xp.uint32))
    qv = counter_mod(xp, key["qsafe"], qctr, plan.n)
    wctr = xp.arange(W, dtype=xp.uint32)
    qcoin = counter_u01(xp, key["qraw"], wctr) < np.float32(plan.qraw_rate)
    qrv = counter_mod(xp, key["qrawv"], wctr, plan.n)
    raw_line = xp.where(qcoin, vline(0, qrv), -1)
    cwr = xp.concatenate([fline(plan.frontier_base, qv), raw_line[:, None]], 1)

    pool = counter_mod(xp, key["pool"],
                       xp.arange(plan.pool_n, dtype=xp.uint32), plan.n)
    cctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.reads_n)
            + xp.arange(plan.reads_n, dtype=xp.uint32))
    cv = pool[counter_mod(xp, key["crs"], cctr, plan.pool_n)]
    half = plan.reads_n // 2
    crd = xp.concatenate([vline(0, cv[:, :half]),
                          fline(plan.frontier_base, cv[:, half:])], 1)

    return _finish_arrays(xp, plan, reads, writes, cwr, crd, kid, start, end, pre)


def _htap_arrays(plan: HtapPlan, keys):
    """Seed HTAP family (select scans + hash-join probes vs transactions)."""
    xp = jnp
    key = dict(zip(HtapPlan.STREAMS, keys))
    W, K = plan.num_windows, plan.num_kernels
    TL = plan.tuple_lines
    kid, j, start, end = _kernel_structure(xp, plan)

    table = counter_mod(xp, key["tbl"], xp.arange(K, dtype=xp.uint32),
                        plan.tables)
    cur0 = counter_mod(xp, key["cur"], xp.arange(K, dtype=xp.uint32),
                       max(1, plan.tuples - 1))

    # inter-kernel txn-commit burst, biased toward the scanned (hot) table
    bctr = (xp.arange(K, dtype=xp.uint32)[:, None] * np.uint32(plan.burst_n)
            + xp.arange(plan.burst_n, dtype=xp.uint32))
    btab = counter_mod(xp, key["btab"], bctr, plan.tables)
    btab = xp.where(xp.arange(plan.burst_n)[None, :] < plan.burst_hot,
                    table[:, None], btab)
    btup = counter_mod(xp, key["btup"], bctr, plan.tuples)
    bfld = counter_mod(xp, key["bfld"], bctr, TL)
    pre = xp.zeros((K, plan.total_lines), bool)
    pre = pre.at[xp.arange(K, dtype=xp.int32)[:, None],
                 tline(plan, btab, btup, bfld)].set(True)

    # analytics: sequential select scan + random hash-join probes
    s = xp.arange(plan.n_scan, dtype=xp.int32)
    tup = (cur0[kid][:, None] + (j * (plan.n_scan // TL))[:, None]
           + s[None, :] // TL) % plan.tuples
    scan = tline(plan, table[kid][:, None], tup, s[None, :] % TL)
    pctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.n_probe)
            + xp.arange(plan.n_probe, dtype=xp.uint32))
    probe = plan.hash_base + counter_mod(xp, key["probe"], pctr, plan.hash_lines)
    reads = xp.concatenate([scan, probe], 1)
    wctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.n_wr)
            + xp.arange(plan.n_wr, dtype=xp.uint32))
    writes = plan.hash_base + counter_mod(xp, key["wrh"], wctr, plan.hash_lines)

    # transactions: a few tuple writes (hot-table-biased) + cached reads
    tctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.txn_writes)
            + xp.arange(plan.txn_writes, dtype=xp.uint32))
    ttab = counter_mod(xp, key["twtab"], tctr, plan.tables)
    ttab = xp.where(xp.arange(plan.txn_writes)[None, :] < plan.txn_hot,
                    table[kid][:, None], ttab)
    ttup = counter_mod(xp, key["twtup"], tctr, plan.tuples)
    tfld = counter_mod(xp, key["twfld"], tctr, TL)
    cwr = tline(plan, ttab, ttup, tfld)

    ictr = xp.arange(plan.pool_n, dtype=xp.uint32)
    pool = tline(plan, counter_mod(xp, key["ptab"], ictr, plan.tables),
                 counter_mod(xp, key["ptup"], ictr, plan.tuples),
                 counter_mod(xp, key["pfld"], ictr, TL))
    rctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.txn_reads)
            + xp.arange(plan.txn_reads, dtype=xp.uint32))
    crd = pool[counter_mod(xp, key["txr"], rctr, plan.pool_n)]

    return _finish_arrays(xp, plan, reads, writes, cwr, crd, kid, start, end, pre)


def _stream_arrays(plan: StreamPlan, keys):
    """Streaming-ingest HTAP: appends at a moving tail, analytics over the
    recently-ingested region (tail - lag), reuse-heavy hot-tail txn reads."""
    xp = jnp
    key = dict(zip(StreamPlan.STREAMS, keys))
    W, K, TL, TOT = plan.num_windows, plan.num_kernels, plan.tuple_lines, \
        plan.total_tuples
    kid, j, start, end = _kernel_structure(xp, plan)
    w32 = xp.arange(W, dtype=xp.int32)
    tail = (w32 * plan.apw) % TOT                             # (W,)

    # analytics: scan the tuples ingested `lag` tuples ago + hash probes
    s = xp.arange(plan.n_scan, dtype=xp.int32)
    g_scan = (tail[:, None] + TOT - plan.lag - s[None, :]) % TOT
    scan = gtline(plan, g_scan, s[None, :] % TL)
    pctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.n_probe)
            + xp.arange(plan.n_probe, dtype=xp.uint32))
    probe = plan.hash_base + counter_mod(xp, key["probe"], pctr, plan.hash_lines)
    reads = xp.concatenate([scan, probe], 1)
    wctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.n_wr)
            + xp.arange(plan.n_wr, dtype=xp.uint32))
    writes = plan.hash_base + counter_mod(xp, key["wrh"], wctr, plan.hash_lines)

    # transactions: append new tuples AT the tail (the hot-tail writes the
    # analytics will scan `lag` later) + index maintenance in the hash area
    a = xp.arange(plan.apw, dtype=xp.int32)
    g_app = (tail[:, None] + a[None, :]) % TOT
    appends = gtline(plan, g_app, xp.zeros_like(g_app))
    ictr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.idx_writes)
            + xp.arange(plan.idx_writes, dtype=xp.uint32))
    idxw = plan.hash_base + counter_mod(xp, key["idxw"], ictr, plan.hash_lines)
    cwr = xp.concatenate([appends, idxw], 1)

    # txn reads: the recently-ingested window behind the tail (reuse-heavy —
    # NC pays DRAM for every one of them, every window)
    rctr = (xp.arange(W, dtype=xp.uint32)[:, None] * np.uint32(plan.txn_reads)
            + xp.arange(plan.txn_reads, dtype=xp.uint32))
    r = counter_mod(xp, key["txr"], rctr, plan.recent)
    g_rd = (tail[:, None] + TOT - 1 - r) % TOT
    crd = gtline(plan, g_rd, r % TL)

    # inter-kernel commit burst just behind the tail
    bctr = (xp.arange(K, dtype=xp.uint32)[:, None] * np.uint32(plan.burst_n)
            + xp.arange(plan.burst_n, dtype=xp.uint32))
    tail_k = (xp.arange(K, dtype=xp.int32) * plan.wpk * plan.apw) % TOT
    b = counter_mod(xp, key["burst"], bctr, 64)
    g_b = (tail_k[:, None] + TOT - 1 - b) % TOT
    pre = xp.zeros((K, plan.total_lines), bool)
    pre = pre.at[xp.arange(K, dtype=xp.int32)[:, None],
                 gtline(plan, g_b, xp.zeros_like(g_b))].set(True)

    return _finish_arrays(xp, plan, reads, writes, cwr, crd, kid, start, end, pre)


def _mt_arrays(plan: MTPlan, keys, edges):
    """Multi-tenant mix: tenants alternate kernels; both tenants' processor
    threads write every window (cross-kernel CPUWriteSet pressure)."""
    xp = jnp
    key = dict(zip(MTPlan.STREAMS, keys))
    W, K, epw = plan.num_windows, plan.num_kernels, plan.epw
    kid, j, start, end = _kernel_structure(xp, plan)
    tenant_b = (kid % 2) == 1                                 # (W,) bool
    kl = kid // 2                                             # tenant-local kernel

    ka, kb = len(plan.hi_a), len(plan.hi_b)
    e0a = counter_mod(xp, key["e0A"], xp.arange(ka, dtype=xp.uint32),
                      np.asarray(plan.hi_a, np.uint32))
    e0b = counter_mod(xp, key["e0B"], xp.arange(kb, dtype=xp.uint32),
                      np.asarray(plan.hi_b, np.uint32))
    e0 = xp.where(tenant_b, e0b[xp.clip(kl, 0, kb - 1)],
                  e0a[xp.clip(kl, 0, ka - 1)])

    # active tenant's edgeMap over the shared CSR edges, private vertex arrays
    pc = xp.where(tenant_b, plan.b_pc, plan.a_pc)[:, None]
    pn = xp.where(tenant_b, plan.b_pn, plan.a_pn)[:, None]
    lo = e0 + j * epw
    eidx = (lo[:, None] + xp.arange(epw, dtype=xp.int32)) % plan.E
    src = edges[eidx, 0]
    dst = edges[eidx, 1]
    reads = xp.zeros((W, 2 * epw), xp.int32)
    reads = reads.at[:, 0::2].set(eline(plan.edge_base, eidx))
    reads = reads.at[:, 1::2].set((pc + dst // VPL).astype(xp.int32))
    # tenant A is pagerank-like (writes p_next[src]); B label-propagation
    writes = (pn + xp.where(tenant_b[:, None], dst, src) // VPL).astype(xp.int32)

    # per-kernel bookkeeping pre-writes in the active tenant's region
    bka = counter_mod(xp, key["bkA"], xp.arange(ka * plan.bk_n, dtype=xp.uint32),
                      plan.n).reshape(ka, plan.bk_n)
    bkb = counter_mod(xp, key["bkB"], xp.arange(kb * plan.bk_n, dtype=xp.uint32),
                      plan.n).reshape(kb, plan.bk_n)
    pre = xp.zeros((K, plan.total_lines), bool)
    ks = xp.arange(K, dtype=xp.int32)
    bsel = (ks % 2) == 1
    bk = xp.where(bsel[:, None],
                  bkb[xp.clip(ks // 2, 0, kb - 1)],
                  bka[xp.clip(ks // 2, 0, ka - 1)])
    # bookkeeping lands in frontier + p_next (next-iteration output merge):
    # WAW-safe under coarse-grained atomicity, but still CPUWriteSet volume
    frb = xp.where(bsel, plan.b_fr, plan.a_fr)[:, None]
    pnb = xp.where(bsel, plan.b_pn, plan.a_pn)[:, None]
    pre_lines = xp.concatenate([(frb + bk // 64).astype(xp.int32),
                                (pnb + bk // VPL).astype(xp.int32)], 1)
    pre = pre.at[ks[:, None], pre_lines].set(True)

    # BOTH tenants' threads are live every window: A's uniform RAW writes +
    # B's hot-vertex RAW writes + one safe p_next write each.
    wctr = xp.arange(W, dtype=xp.uint32)
    a_coin = counter_u01(xp, key["rawnA"], wctr) < np.float32(plan.a_raw_frac)
    a_v = counter_mod(xp, key["rawuniA"], wctr, plan.n)
    a_raw = xp.where(a_coin, plan.a_pc + a_v // VPL, -1)
    a_safe = plan.a_pn + counter_mod(xp, key["safeA"], wctr, plan.n) // VPL
    Rb = plan.b_raw_int + 1
    bctr = (wctr[:, None] * np.uint32(Rb) + xp.arange(Rb, dtype=xp.uint32))
    b_coin = counter_u01(xp, key["rawnB"], wctr) < np.float32(plan.b_raw_frac)
    b_valid = (xp.arange(Rb, dtype=xp.int32) < plan.b_raw_int) | \
        ((xp.arange(Rb, dtype=xp.int32) == plan.b_raw_int) & b_coin[:, None])
    b_hot = counter_u01(xp, key["rawhotB"], bctr) < np.float32(plan.b_hot_bias)
    b_vh = edges[counter_mod(xp, key["rawhotvB"], bctr, plan.E), 1]
    b_vu = counter_mod(xp, key["rawuniB"], bctr, plan.n)
    b_raw = xp.where(b_valid, plan.b_pc + xp.where(b_hot, b_vh, b_vu) // VPL, -1)
    b_safe = plan.b_pn + counter_mod(xp, key["safeB"], wctr, plan.n) // VPL
    cwr = xp.concatenate([a_raw[:, None], a_safe[:, None].astype(xp.int32),
                          b_raw, b_safe[:, None].astype(xp.int32)], 1)

    # cached reads from both tenants' hot pools
    poolA = counter_mod(xp, key["poolA"],
                        xp.arange(plan.pool_n, dtype=xp.uint32), plan.n)
    poolB = counter_mod(xp, key["poolB"],
                        xp.arange(plan.pool_n, dtype=xp.uint32), plan.n)
    per = plan.reads_n // 2
    cctr = (wctr[:, None] * np.uint32(per) + xp.arange(per, dtype=xp.uint32))
    av = poolA[counter_mod(xp, key["crsA"], cctr, plan.pool_n)]
    bv = poolB[counter_mod(xp, key["crsB"], cctr, plan.pool_n)]
    q = per // 2
    crd = xp.concatenate([
        (plan.a_pn + av[:, :q] // VPL).astype(xp.int32),
        (plan.a_fr + av[:, q:] // 64).astype(xp.int32),
        (plan.b_pn + bv[:, :q] // VPL).astype(xp.int32),
        (plan.b_fr + bv[:, q:] // 64).astype(xp.int32)], 1)

    return _finish_arrays(xp, plan, reads, writes, cwr, crd, kid, start, end, pre)


# ---------------------------------------------------------------------------
# Compiled entry points
# ---------------------------------------------------------------------------

_ARRAY_FNS = {
    GraphPlan: _graph_arrays,
    FrontierPlan: _frontier_arrays,
    HtapPlan: _htap_arrays,
    StreamPlan: _stream_arrays,
    MTPlan: _mt_arrays,
}


@functools.lru_cache(maxsize=64)
def _compiled(plan):
    """One jitted tensor program per plan (bounded, like ``make_graph`` —
    plan-field sweeps shouldn't pin executables forever).  Threefry keys
    (and the edge array, where the family has one) are traced arguments, so
    regenerating at another seed reuses the compile."""
    fn = _ARRAY_FNS[type(plan)]
    if type(plan) in (HtapPlan, StreamPlan):
        return jax.jit(lambda keys: fn(plan, keys))
    return jax.jit(lambda keys, edges: fn(plan, keys, edges))


def generator(plan, seed: int = 0, edges: jax.Array | None = None):
    """(fn, args) producing the full trace-array dict on device — the unit
    the trace-synthesis benchmark times (compile excluded).  ``edges`` is the
    graph's device-resident array (:func:`repro.sim.graphs.make_graph`), so
    synthesis puts no edge bytes."""
    keys = jnp.asarray(derive_keys(
        plan.app, getattr(plan, "graph_name", None), seed, type(plan).STREAMS))
    fn = _compiled(plan)
    if type(plan) in (HtapPlan, StreamPlan):
        return fn, (keys,)
    return fn, (keys, edges)


def synthesize(plan, seed: int = 0, edges: jax.Array | None = None) -> dict:
    """Run the compiled generator; returns the device-array dict."""
    fn, args = generator(plan, seed, edges)
    return fn(*args)
