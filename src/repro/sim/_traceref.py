"""Sequential numpy reference for trace synthesis (the differential twin).

This module preserves the seed repo's trace-generation *style* — host-side
numpy, one Python loop iteration per kernel/window — as the readable
specification of every workload family, while drawing randomness from the
same audited counter-based streams (:func:`repro.sim.synth.derive_key`,
Threefry-2x32) as the jit-compiled JAX generators in
:mod:`repro.sim.synth`.  Because all per-element math is shared (the draw
helpers, line-layout arithmetic and instruction-count formulas are
parameterized over the array namespace), the JAX path must regenerate every
workload produced here **bit-identically** — same seeds, same arrays, every
``WindowTrace`` field — which ``tests/test_trace_synth.py`` asserts.  This
is the same differential discipline ``core/_boolref.py`` established for
the simulator.

It is also the baseline of the trace-synthesis throughput benchmark
(``benchmarks/bench_engine.py`` → ``BENCH_engine.json:trace_synth``): the
per-window Python loops are what on-device generation replaces.
"""

from __future__ import annotations

import numpy as np

from repro.runtime import spans
from repro.sim import synth as S
from repro.sim.synth import (
    AR,
    AW,
    BR,
    BW,
    VPL,
    counter_mod,
    counter_u01,
    derive_keys,
    eline,
    fline,
    gtline,
    instr_counts,
    tline,
    vline,
)


def _pad(ids: np.ndarray, width: int) -> np.ndarray:
    out = np.full((width,), -1, dtype=np.int32)
    n = min(len(ids), width)
    out[:n] = ids[:n]
    return out


def _u32(*vals) -> np.ndarray:
    return np.asarray(vals, np.uint32)


def _arange32(n: int, base: int = 0) -> np.ndarray:
    return (np.arange(n, dtype=np.uint32) + np.uint32(base)).astype(np.uint32)


def _alloc(plan):
    W = plan.num_windows
    return (np.full((W, AR), -1, np.int32), np.full((W, AW), -1, np.int32),
            np.full((W, BR), -1, np.int32), np.full((W, BW), -1, np.int32),
            np.zeros((plan.num_kernels, plan.total_lines), bool))


def _finish(plan, pim_reads, pim_writes, cpu_reads, cpu_writes, pre):
    """Kernel structure + shared instruction-count formulas -> field dict."""
    K, wpk = plan.num_kernels, plan.wpk
    n_pim = ((pim_reads >= 0).sum(1) + (pim_writes >= 0).sum(1)).astype(np.int32)
    n_cpu = ((cpu_reads >= 0).sum(1) + (cpu_writes >= 0).sum(1)).astype(np.int32)
    pim_i, cpu_i, priv = instr_counts(np, plan, n_pim, n_cpu)
    kernel_id = np.repeat(np.arange(K, dtype=np.int32), wpk)
    start = np.zeros((K * wpk,), bool)
    start[::wpk] = True
    end = np.zeros((K * wpk,), bool)
    end[wpk - 1 :: wpk] = True
    return dict(pim_reads=pim_reads, pim_writes=pim_writes,
                cpu_reads=cpu_reads, cpu_writes=cpu_writes,
                kernel_id=kernel_id, kernel_start=start, kernel_end=end,
                pre_writes=pre, pim_instr=pim_i, cpu_instr=cpu_i,
                cpu_priv_accesses=priv)


# ---------------------------------------------------------------------------
# Seed graph family (Ligra edgeMap)
# ---------------------------------------------------------------------------


def graph_arrays_ref(plan: S.GraphPlan, keys, edges) -> dict:
    key = dict(zip(S.GraphPlan.STREAMS, np.asarray(keys)))
    epw, R = plan.epw, plan.raw_max
    pim_reads, pim_writes, cpu_reads, cpu_writes, pre = _alloc(plan)

    hi = np.asarray(plan.hi, np.uint32)
    pool = counter_mod(np, key["pool"], _arange32(plan.pool_n), plan.n)

    w = 0
    for k in range(plan.num_kernels):
        e0 = int(counter_mod(np, key["e0"], _u32(k), hi[k : k + 1])[0])
        bk = counter_mod(np, key["bk"], _arange32(plan.bk_n, k * plan.bk_n),
                         plan.n)
        pre[k, np.concatenate([fline(plan.frontier_base, bk), vline(0, bk)])] = True

        for j in range(plan.wpk):
            # edgeMap: sequential edge-array lines + scattered p_curr gathers
            eidx = (np.arange(epw, dtype=np.int32) + np.int32(e0 + j * epw)) % plan.E
            src, dst = edges[eidx, 0], edges[eidx, 1]
            reads = np.empty((2 * epw,), np.int32)
            reads[0::2] = eline(plan.edge_base, eidx)
            reads[1::2] = vline(0, dst)
            pim_reads[w] = _pad(reads, AR)
            pim_writes[w] = _pad(
                vline(plan.p_next_base, src if plan.writes_src else dst), AW)

            # concurrent RAW-capable p_curr writes + one safe p_next write
            rctr = _arange32(R, w * R)
            coin = counter_u01(np, key["rawn"], _u32(w))[0] < np.float32(plan.raw_frac)
            rvalid = (np.arange(R) < plan.raw_int) | \
                ((np.arange(R) == plan.raw_int) & coin)
            hot = counter_u01(np, key["rawhot"], rctr) < np.float32(plan.hot_bias)
            v_hot = edges[counter_mod(np, key["rawhotv"], rctr, plan.E), 1]
            v_uni = counter_mod(np, key["rawuni"], rctr, plan.n)
            raw_lines = np.where(rvalid, vline(0, np.where(hot, v_hot, v_uni)), -1)
            safe_v = counter_mod(np, key["safe"], _u32(w), plan.n)
            cpu_writes[w] = _pad(
                np.concatenate([raw_lines, vline(plan.p_next_base, safe_v)]), BW)

            # cached bookkeeping reads from the stable hot-vertex pool
            cctr = _arange32(plan.reads_n, w * plan.reads_n)
            cv = pool[counter_mod(np, key["crs"], cctr, plan.pool_n)]
            half = plan.reads_n // 2
            cpu_reads[w] = _pad(
                np.concatenate([vline(plan.p_next_base, cv[:half]),
                                fline(plan.frontier_base, cv[half:])]), BR)
            w += 1

    return _finish(plan, pim_reads, pim_writes, cpu_reads, cpu_writes, pre)


# ---------------------------------------------------------------------------
# BFS/SSSP frontier family
# ---------------------------------------------------------------------------


def frontier_arrays_ref(plan: S.FrontierPlan, keys, edges) -> dict:
    key = dict(zip(S.FrontierPlan.STREAMS, np.asarray(keys)))
    Smax = plan.epw_max
    pim_reads, pim_writes, cpu_reads, cpu_writes, pre = _alloc(plan)
    pool = counter_mod(np, key["pool"], _arange32(plan.pool_n), plan.n)

    w = 0
    for k in range(plan.num_kernels):
        f0 = int(counter_mod(np, key["f0"], _u32(k), plan.E)[0])
        bk = counter_mod(np, key["bk"], _arange32(plan.bk_n, k * plan.bk_n),
                         plan.n)
        pre[k, np.concatenate([fline(plan.frontier_base, bk), vline(0, bk)])] = True
        epw = plan.epw[k]

        for j in range(plan.wpk):
            # level-sized frontier sweep: slots past the frontier stay -1
            slot = np.arange(Smax, dtype=np.int32)
            alive = slot < epw
            eidx = (slot + np.int32(f0 + j * epw)) % plan.E
            dst = edges[eidx, 1]
            reads = np.empty((2 * Smax,), np.int32)
            reads[0::2] = np.where(alive, eline(plan.edge_base, eidx), -1)
            reads[1::2] = np.where(alive, vline(0, dst), -1)
            pim_reads[w] = _pad(reads, AR)
            relaxed = counter_u01(np, key["relax"], _arange32(Smax, w * Smax)) \
                < np.float32(plan.relax_rate)
            pim_writes[w] = _pad(
                np.where(alive & relaxed, vline(plan.p_next_base, dst), -1), AW)

            # frontier-queue writes (safe) + occasional dist relaxation (RAW)
            qv = counter_mod(np, key["qsafe"], _arange32(2, w * 2), plan.n)
            qcoin = counter_u01(np, key["qraw"], _u32(w))[0] < np.float32(plan.qraw_rate)
            qrv = counter_mod(np, key["qrawv"], _u32(w), plan.n)
            raw_line = np.where(qcoin, vline(0, qrv), -1)
            cpu_writes[w] = _pad(
                np.concatenate([fline(plan.frontier_base, qv), raw_line]), BW)

            cctr = _arange32(plan.reads_n, w * plan.reads_n)
            cv = pool[counter_mod(np, key["crs"], cctr, plan.pool_n)]
            half = plan.reads_n // 2
            cpu_reads[w] = _pad(
                np.concatenate([vline(0, cv[:half]),
                                fline(plan.frontier_base, cv[half:])]), BR)
            w += 1

    return _finish(plan, pim_reads, pim_writes, cpu_reads, cpu_writes, pre)


# ---------------------------------------------------------------------------
# Seed HTAP family
# ---------------------------------------------------------------------------


def htap_arrays_ref(plan: S.HtapPlan, keys) -> dict:
    key = dict(zip(S.HtapPlan.STREAMS, np.asarray(keys)))
    TL = plan.tuple_lines
    pim_reads, pim_writes, cpu_reads, cpu_writes, pre = _alloc(plan)

    ictr = _arange32(plan.pool_n)
    pool = tline(plan, counter_mod(np, key["ptab"], ictr, plan.tables),
                 counter_mod(np, key["ptup"], ictr, plan.tuples),
                 counter_mod(np, key["pfld"], ictr, TL))

    w = 0
    for k in range(plan.num_kernels):
        table = int(counter_mod(np, key["tbl"], _u32(k), plan.tables)[0])
        cur0 = int(counter_mod(np, key["cur"], _u32(k),
                               max(1, plan.tuples - 1))[0])
        # txn-commit burst, biased toward the (hot) scanned table
        bctr = _arange32(plan.burst_n, k * plan.burst_n)
        btab = counter_mod(np, key["btab"], bctr, plan.tables)
        btab = np.where(np.arange(plan.burst_n) < plan.burst_hot, table, btab)
        btup = counter_mod(np, key["btup"], bctr, plan.tuples)
        bfld = counter_mod(np, key["bfld"], bctr, TL)
        pre[k, tline(plan, btab, btup, bfld)] = True

        for j in range(plan.wpk):
            # select scan (sequential tuple lines) + random hash-join probes
            s = np.arange(plan.n_scan, dtype=np.int32)
            tup = (cur0 + j * (plan.n_scan // TL) + s // TL) % plan.tuples
            scan = tline(plan, np.full_like(s, table), tup, s % TL)
            pctr = _arange32(plan.n_probe, w * plan.n_probe)
            probe = plan.hash_base + counter_mod(np, key["probe"], pctr,
                                                 plan.hash_lines)
            pim_reads[w] = _pad(np.concatenate([scan, probe]), AR)
            wctr = _arange32(plan.n_wr, w * plan.n_wr)
            pim_writes[w] = _pad(
                plan.hash_base + counter_mod(np, key["wrh"], wctr,
                                             plan.hash_lines), AW)

            # transactions: hot-table-biased tuple writes + cached reads
            tctr = _arange32(plan.txn_writes, w * plan.txn_writes)
            ttab = counter_mod(np, key["twtab"], tctr, plan.tables)
            ttab = np.where(np.arange(plan.txn_writes) < plan.txn_hot,
                            table, ttab)
            ttup = counter_mod(np, key["twtup"], tctr, plan.tuples)
            tfld = counter_mod(np, key["twfld"], tctr, TL)
            cpu_writes[w] = _pad(tline(plan, ttab, ttup, tfld), BW)
            rctr = _arange32(plan.txn_reads, w * plan.txn_reads)
            cpu_reads[w] = _pad(
                pool[counter_mod(np, key["txr"], rctr, plan.pool_n)], BR)
            w += 1

    return _finish(plan, pim_reads, pim_writes, cpu_reads, cpu_writes, pre)


# ---------------------------------------------------------------------------
# Streaming-ingest HTAP family
# ---------------------------------------------------------------------------


def stream_arrays_ref(plan: S.StreamPlan, keys) -> dict:
    key = dict(zip(S.StreamPlan.STREAMS, np.asarray(keys)))
    TL, TOT = plan.tuple_lines, plan.total_tuples
    pim_reads, pim_writes, cpu_reads, cpu_writes, pre = _alloc(plan)

    for k in range(plan.num_kernels):
        # commit burst just behind the tail at kernel start
        tail_k = (k * plan.wpk * plan.apw) % TOT
        bctr = _arange32(plan.burst_n, k * plan.burst_n)
        b = counter_mod(np, key["burst"], bctr, 64)
        g_b = (tail_k + TOT - 1 - b) % TOT
        pre[k, gtline(plan, g_b, np.zeros_like(g_b))] = True

    for w in range(plan.num_windows):
        tail = (w * plan.apw) % TOT
        # analytics: scan the tuples ingested `lag` ago + hash probes
        s = np.arange(plan.n_scan, dtype=np.int32)
        g_scan = (tail + TOT - plan.lag - s) % TOT
        scan = gtline(plan, g_scan, s % TL)
        pctr = _arange32(plan.n_probe, w * plan.n_probe)
        probe = plan.hash_base + counter_mod(np, key["probe"], pctr,
                                             plan.hash_lines)
        pim_reads[w] = _pad(np.concatenate([scan, probe]), AR)
        wctr = _arange32(plan.n_wr, w * plan.n_wr)
        pim_writes[w] = _pad(
            plan.hash_base + counter_mod(np, key["wrh"], wctr,
                                         plan.hash_lines), AW)

        # txns: append at the tail + index maintenance in the hash area
        a = np.arange(plan.apw, dtype=np.int32)
        appends = gtline(plan, (tail + a) % TOT, np.zeros_like(a))
        ictr = _arange32(plan.idx_writes, w * plan.idx_writes)
        idxw = plan.hash_base + counter_mod(np, key["idxw"], ictr,
                                            plan.hash_lines)
        cpu_writes[w] = _pad(np.concatenate([appends, idxw]), BW)

        # reuse-heavy hot reads of the recently-ingested region
        rctr = _arange32(plan.txn_reads, w * plan.txn_reads)
        r = counter_mod(np, key["txr"], rctr, plan.recent)
        cpu_reads[w] = _pad(gtline(plan, (tail + TOT - 1 - r) % TOT, r % TL), BR)

    return _finish(plan, pim_reads, pim_writes, cpu_reads, cpu_writes, pre)


# ---------------------------------------------------------------------------
# Multi-tenant mix
# ---------------------------------------------------------------------------


def mt_arrays_ref(plan: S.MTPlan, keys, edges) -> dict:
    key = dict(zip(S.MTPlan.STREAMS, np.asarray(keys)))
    epw = plan.epw
    pim_reads, pim_writes, cpu_reads, cpu_writes, pre = _alloc(plan)
    poolA = counter_mod(np, key["poolA"], _arange32(plan.pool_n), plan.n)
    poolB = counter_mod(np, key["poolB"], _arange32(plan.pool_n), plan.n)
    hi_a = np.asarray(plan.hi_a, np.uint32)
    hi_b = np.asarray(plan.hi_b, np.uint32)
    Rb = plan.b_raw_int + 1

    w = 0
    for k in range(plan.num_kernels):
        tb, kl = (k % 2) == 1, k // 2
        if tb:
            e0 = int(counter_mod(np, key["e0B"], _u32(kl), hi_b[kl : kl + 1])[0])
            bk = counter_mod(np, key["bkB"],
                             _arange32(plan.bk_n, kl * plan.bk_n), plan.n)
            pc, pn, fr = plan.b_pc, plan.b_pn, plan.b_fr
        else:  # tenant A
            e0 = int(counter_mod(np, key["e0A"], _u32(kl), hi_a[kl : kl + 1])[0])
            bk = counter_mod(np, key["bkA"],
                             _arange32(plan.bk_n, kl * plan.bk_n), plan.n)
            pc, pn, fr = plan.a_pc, plan.a_pn, plan.a_fr
        # bookkeeping: frontier + p_next (next-iteration output merge)
        pre[k, np.concatenate([np.int32(fr) + bk // 64,
                               np.int32(pn) + bk // VPL])] = True

        for j in range(plan.wpk):
            # active tenant's edgeMap over the shared CSR edge array
            eidx = (np.arange(epw, dtype=np.int32) + np.int32(e0 + j * epw)) % plan.E
            src, dst = edges[eidx, 0], edges[eidx, 1]
            reads = np.empty((2 * epw,), np.int32)
            reads[0::2] = eline(plan.edge_base, eidx)
            reads[1::2] = np.int32(pc) + dst // VPL
            pim_reads[w] = _pad(reads, AR)
            pim_writes[w] = _pad(np.int32(pn) + (dst if tb else src) // VPL, AW)

            # BOTH tenants' threads write every window
            a_coin = counter_u01(np, key["rawnA"], _u32(w))[0] < np.float32(plan.a_raw_frac)
            a_v = counter_mod(np, key["rawuniA"], _u32(w), plan.n)
            a_raw = np.where(a_coin, plan.a_pc + a_v // VPL, -1)
            a_safe = plan.a_pn + counter_mod(np, key["safeA"], _u32(w), plan.n) // VPL
            bctr = _arange32(Rb, w * Rb)
            b_coin = counter_u01(np, key["rawnB"], _u32(w))[0] < np.float32(plan.b_raw_frac)
            b_valid = (np.arange(Rb) < plan.b_raw_int) | \
                ((np.arange(Rb) == plan.b_raw_int) & b_coin)
            b_hot = counter_u01(np, key["rawhotB"], bctr) < np.float32(plan.b_hot_bias)
            b_vh = edges[counter_mod(np, key["rawhotvB"], bctr, plan.E), 1]
            b_vu = counter_mod(np, key["rawuniB"], bctr, plan.n)
            b_raw = np.where(b_valid,
                             plan.b_pc + np.where(b_hot, b_vh, b_vu) // VPL, -1)
            b_safe = plan.b_pn + counter_mod(np, key["safeB"], _u32(w), plan.n) // VPL
            cpu_writes[w] = _pad(np.concatenate(
                [a_raw, a_safe, b_raw, b_safe]).astype(np.int32), BW)

            # cached reads from both tenants' hot pools
            per = plan.reads_n // 2
            cctr = _arange32(per, w * per)
            av = poolA[counter_mod(np, key["crsA"], cctr, plan.pool_n)]
            bv = poolB[counter_mod(np, key["crsB"], cctr, plan.pool_n)]
            q = per // 2
            cpu_reads[w] = _pad(np.concatenate([
                plan.a_pn + av[:q] // VPL, plan.a_fr + av[q:] // 64,
                plan.b_pn + bv[:q] // VPL, plan.b_fr + bv[q:] // 64,
            ]).astype(np.int32), BR)
            w += 1

    return _finish(plan, pim_reads, pim_writes, cpu_reads, cpu_writes, pre)


ARRAY_FNS_REF = {
    S.GraphPlan: graph_arrays_ref,
    S.FrontierPlan: frontier_arrays_ref,
    S.HtapPlan: htap_arrays_ref,
    S.StreamPlan: stream_arrays_ref,
    S.MTPlan: mt_arrays_ref,
}


def synthesize_ref(plan, seed: int = 0, edges=None) -> dict:
    """Generate the full trace-array dict with the sequential numpy loops;
    a device-resident ``edges`` is read to the host (and counted)."""
    keys = derive_keys(plan.app, getattr(plan, "graph_name", None), seed,
                       type(plan).STREAMS)
    fn = ARRAY_FNS_REF[type(plan)]
    if type(plan) in (S.HtapPlan, S.StreamPlan):
        return fn(plan, keys)
    return fn(plan, keys, spans.d2h(edges))
