"""Plain numpy simulation of the six PIM coherence mechanisms (paper §3.2,
§4-§5): the benchmark's reference for every simulated result.

It imports nothing of the simulator under test.  State is held as boolean
arrays over the lines a trace ever touches (compact indices), signatures as
boolean Bloom images of ``SIG_BITS`` bits in ``SEGMENTS`` H3 segments, and
the CPUWriteSet bank is formed line by line (register = line id mod 16).
One window is one loop iteration.

Arithmetic: every floating-point operation is rounded through ``q``.  The
configurations state float32 (``q = np.float32``, the reference); the
control rounds every operation to bfloat16 instead (``precision="bfloat16"``),
the step below float32.

``simulate(trace, hw, mechanism, lazy, precision)`` returns the result
fields as a dict of floats; ``hw`` and ``lazy`` are dicts of the model's
parameters (``HW_DEFAULTS`` / ``LAZY_DEFAULTS`` updated by the study).
"""

from __future__ import annotations

import functools

import ml_dtypes
import numpy as np

LINE = 64
CTRL = 8
SIG_BITS, SEGMENTS, SEG_BITS = 2048, 4, 512
REGS = 16
H3_SEED = 0xC0FFEE

# Table 1 system (16 cores, 2 MB L2, one HMC cube) and the model constants.
HW_DEFAULTS = dict(
    cpu_cores=16, pim_cores=16, freq_ghz=2.0, cpu_ipc=4.0, pim_ipc=0.8,
    cpu_mlp=4.0, cpu_kernel_mlp=1.8, l1_hit_ns=0.5, l2_hit_ns=5.0,
    offchip_mem_ns=110.0, pim_mem_ns=48.0, offchip_msg_ns=25.0,
    fg_msg_exposed_ns=20.0, offchip_bw_gbs=32.0, thread_cache_cap=16384,
    cpu_only_cache_cap=4096, nc_bytes=32, nc_dram_energy_factor=3.0)
LAZY_DEFAULTS = dict(partial_commits=True, use_dbi=True,
                     dbi_interval_cycles=1600.0, dbi_lines_per_fire=128,
                     commit_exposure=0.15)

FIELDS = ("time_ns", "offchip_bytes", "dram_bytes", "l1_accesses",
          "l2_accesses", "commits", "conflicts_sig", "conflicts_exact",
          "rollbacks", "flush_lines", "blocked_accesses", "dbi_writebacks",
          "sig_bytes")
MECHANISMS = ("cpu", "fg", "cg", "nc", "lazypim", "ideal")


def rounding(precision: str):
    if precision == "float32":
        return np.float32
    if precision == "bfloat16":
        return lambda x: np.float32(ml_dtypes.bfloat16(x))
    raise ValueError(f"unknown precision {precision!r}")


@functools.lru_cache(maxsize=1)
def _h3():
    q = np.random.default_rng(H3_SEED).integers(
        0, SEG_BITS, size=(SEGMENTS, 32)).astype(np.uint32)
    return q


def h3_positions(lines: np.ndarray) -> np.ndarray:
    """(SEGMENTS, n) global signature bit of each line: segment m's H3 hash
    (xor of the matrix rows of the address's set bits) plus m * SEG_BITS."""
    a = lines.astype(np.uint32)
    q = _h3()
    h = np.zeros((SEGMENTS, a.shape[0]), np.uint32)
    for j in range(32):
        bit = ((a >> np.uint32(j)) & np.uint32(1)).astype(bool)
        h ^= np.where(bit[None, :], q[:, j:j + 1], np.uint32(0))
    return (h + (np.arange(SEGMENTS, dtype=np.uint32) * SEG_BITS)[:, None]
            ).astype(np.int64)


def _u01(lines, w, mult, step):
    h = (lines.astype(np.uint64) * mult + w * step) & 0xFFFFFFFF
    return ((h >> np.uint32(16)) & np.uint32(0xFFFF)).astype(np.float32) \
        / np.float32(65536.0)


KNUTH = (2654435761, 40503)
XXH = (2246822519, 374761393)


class Prepared:
    """A trace on compact line indices, with the per-window counts."""

    def __init__(self, tr: dict):
        def valid(a):
            return [row[row >= 0].astype(np.int64) for row in a]

        pr, pw = valid(tr["pim_reads"]), valid(tr["pim_writes"])
        cr, cw = valid(tr["cpu_reads"]), valid(tr["cpu_writes"])
        every = np.concatenate(pr + pw + cr + cw + list(tr["pre_lines"]))
        self.lines = np.unique(every)
        if self.lines.size and (self.lines[0] < 0
                                or self.lines[-1] >= tr["num_lines"]):
            raise ValueError("line id outside the trace's region")
        self.n = self.lines.size

        def ix(x):
            return np.searchsorted(self.lines, x)

        self.pr = [ix(x) for x in pr]
        self.pw = [ix(x) for x in pw]
        self.cr = [ix(x) for x in cr]
        self.cw = [ix(x) for x in cw]
        self.pre = [ix(x) for x in tr["pre_lines"]]
        self.pos = h3_positions(self.lines)
        self.reg = self.lines % REGS
        self.W = len(pr)
        self.kernel_id = np.asarray(tr["kernel_id"])
        self.start = np.asarray(tr["kernel_start"])
        self.end = np.asarray(tr["kernel_end"])
        self.n_pim = [len(a) + len(b) for a, b in zip(pr, pw)]
        self.n_cpu = [len(a) + len(b) for a, b in zip(cr, cw)]
        self.uniq_w = [np.unique(b).size for b in pw]
        self.uniq = [np.unique(np.concatenate([a, b])).size
                     for a, b in zip(pr, pw)]
        self.tr = tr


class _Model:
    """The per-window cost terms shared by the mechanisms."""

    def __init__(self, p: Prepared, hw: dict, q):
        self.p, self.q = p, q
        self.h = {k: q(v) for k, v in hw.items()}
        self.cap = {k: hw[k] for k in ("thread_cache_cap", "cpu_only_cache_cap")}
        tr = p.tr
        self.pim_instr = [q(x) for x in tr["pim_instr"]]
        self.cpu_instr = [q(x) for x in tr["cpu_instr"]]
        self.priv = [q(x) for x in tr["cpu_priv"]]
        self.mr = q(tr["cpu_priv_miss_rate"])
        self.reuse = q(tr["cpu_reuse"])

    def f(self, x):
        return self.q(np.float32(x))

    def pim_compute(self, w):
        h, q = self.h, self.q
        return q(self.pim_instr[w] / q(q(h["pim_cores"] * h["pim_ipc"])
                                       * h["freq_ghz"]))

    def pim_mem(self, w, extra=None):
        h, q = self.h, self.q
        per = h["pim_mem_ns"] if extra is None else q(h["pim_mem_ns"] + extra)
        return q(q(self.f(self.p.uniq[w]) * per) / h["pim_cores"])

    def cpu_compute(self, w):
        h, q = self.h, self.q
        return q(self.cpu_instr[w] / q(q(h["cpu_cores"] * h["cpu_ipc"])
                                       * h["freq_ghz"]))

    def priv_mem(self, w):
        h, q, mr = self.h, self.q, self.mr
        per = q(q(mr * h["offchip_mem_ns"]) + q(q(1.0 - mr) * h["l1_hit_ns"]))
        return q(q(self.priv[w] * per) / h["cpu_cores"])

    def priv_fill(self, w):
        q = self.q
        return q(q(self.priv[w] * self.mr) * LINE)

    def pim_dram(self, w):
        q = self.q
        return q(q(self.f(self.p.uniq[w]) + self.f(self.p.uniq_w[w])) * LINE)

    def bw(self, nbytes):
        return self.q(nbytes / self.h["offchip_bw_gbs"])

    def cpu_step(self, present, dirty, w, cap, cacheable=True):
        """One window of processor accesses to the PIM region: returns
        (hits, misses, mem_ns, fill_bytes); updates present/dirty."""
        h, q, p = self.h, self.q, self.p
        n_acc = self.f(p.n_cpu[w])
        miss_ns = q(h["offchip_mem_ns"] / h["cpu_mlp"])
        if not cacheable:
            n_dyn = q(n_acc * self.reuse)
            return (self.f(0), n_dyn, q(q(n_dyn * miss_ns) / h["cpu_cores"]),
                    q(n_dyn * h["nc_bytes"]))
        cr, cw = p.cr[w], p.cw[w]
        hits = int(present[cr].sum() + present[cw].sum())
        misses = cr.size + cw.size - hits
        present[cr] = True
        present[cw] = True
        dirty[cw] = True
        count = int(present.sum())
        wb = 0
        if count > cap:
            keep = q(np.float32(cap) / np.float32(max(count, 1)))
            keep = min(max(keep, np.float32(0)), np.float32(1))
            idx = np.flatnonzero(present)
            drop = idx[_u01(p.lines[idx], w, *KNUTH) > keep]
            wb = int(dirty[drop].sum())
            present[drop] = False
            dirty[drop] = False
        hits, misses = self.f(hits), self.f(misses)
        repeats = q(q(n_acc * q(self.reuse - 1.0)) * h["l1_hit_ns"])
        mem = q(q(q(q(hits * h["l2_hit_ns"]) + q(misses * miss_ns)) + repeats)
                / h["cpu_cores"])
        fill = q(q(misses + self.f(wb)) * LINE)
        return hits, misses, mem, fill


def _mx(a, b):
    return a if a >= b else b


def _pre(p, w, present, dirty):
    if p.start[w]:
        k = p.pre[p.kernel_id[w]]
        present[k] = True
        dirty[k] = True


def _cpu(m: _Model, acc):
    p, h, q = m.p, m.h, m.q
    present, dirty = np.zeros(p.n, bool), np.zeros(p.n, bool)
    for w in range(p.W):
        _pre(p, w, present, dirty)
        hits, misses, mem, fill = m.cpu_step(present, dirty, w,
                                             m.cap["cpu_only_cache_cap"])
        uniq = m.f(p.uniq[w])
        kern_compute = q(m.pim_instr[w] / q(q(h["cpu_cores"] * h["cpu_ipc"])
                                            * h["freq_ghz"]))
        kern_mem = q(q(uniq * q(h["offchip_mem_ns"] / h["cpu_kernel_mlp"]))
                     / h["cpu_cores"])
        kern_fill = q(q(uniq + m.f(p.uniq_w[w])) * LINE)
        off = q(q(fill + kern_fill) + m.priv_fill(w))
        lat = q(q(q(q(m.cpu_compute(w) + kern_compute) + kern_mem) + mem)
                + m.priv_mem(w))
        acc("time_ns", _mx(lat, m.bw(off)))
        acc("offchip_bytes", off)
        acc("dram_bytes", off)
        acc("l1_accesses", _l1(m, w))
        acc("l2_accesses", q(q(misses + hits) + uniq))


def _l1(m, w):
    q = m.q
    dyn = q(m.f(m.p.n_cpu[w]) * m.reuse)
    return q(q(dyn + m.f(m.p.n_pim[w])) + m.priv[w])


def _ideal(m: _Model, acc):
    p, q = m.p, m.q
    present, dirty = np.zeros(p.n, bool), np.zeros(p.n, bool)
    for w in range(p.W):
        _pre(p, w, present, dirty)
        hits, misses, mem, fill = m.cpu_step(present, dirty, w,
                                             m.cap["thread_cache_cap"])
        present[p.pw[w]] = False
        dirty[p.pw[w]] = False
        pim = q(m.pim_compute(w) + m.pim_mem(w))
        cpu = q(q(m.cpu_compute(w) + mem) + m.priv_mem(w))
        off = q(fill + m.priv_fill(w))
        acc("time_ns", _mx(_mx(pim, cpu), m.bw(off)))
        acc("offchip_bytes", off)
        acc("dram_bytes", q(off + m.pim_dram(w)))
        acc("l1_accesses", _l1(m, w))
        acc("l2_accesses", q(misses + hits))


def _fg(m: _Model, acc):
    p, h, q = m.p, m.h, m.q
    present, dirty = np.zeros(p.n, bool), np.zeros(p.n, bool)
    for w in range(p.W):
        _pre(p, w, present, dirty)
        hits, misses, mem, fill = m.cpu_step(present, dirty, w,
                                             m.cap["thread_cache_cap"])
        pr, pw = p.pr[w], p.pw[w]
        r_dirty, w_dirty = dirty[pr], dirty[pw]
        xfer = m.f(int(r_dirty.sum() + w_dirty.sum()))
        dirty[pr[r_dirty]] = False
        dirty[pw[w_dirty]] = False
        present[pw] = False
        uniq = m.f(p.uniq[w])
        msg = q(q(uniq * 8.0) * CTRL)
        xfer_b = q(xfer * LINE)
        pim = q(q(m.pim_compute(w) + m.pim_mem(w, h["fg_msg_exposed_ns"]))
                + m.bw(xfer_b))
        cpu = q(q(m.cpu_compute(w) + mem) + m.priv_mem(w))
        off = q(q(q(fill + m.priv_fill(w)) + msg) + xfer_b)
        acc("time_ns", _mx(_mx(pim, cpu), m.bw(off)))
        acc("offchip_bytes", off)
        acc("dram_bytes", q(q(fill + m.priv_fill(w)) + m.pim_dram(w)))
        acc("l1_accesses", _l1(m, w))
        acc("l2_accesses", q(q(misses + hits) + uniq))


def _cg(m: _Model, acc):
    p, h, q = m.p, m.h, m.q
    present, dirty = np.zeros(p.n, bool), np.zeros(p.n, bool)
    for w in range(p.W):
        _pre(p, w, present, dirty)
        if p.start[w]:
            n_flush = m.f(int(dirty.sum()))
            dirty[:] = False
            present[:] = False
        else:
            n_flush = m.f(0)
        flush_b = q(n_flush * LINE)
        flush_ns = q(m.bw(flush_b) + (h["offchip_msg_ns"] if p.start[w]
                                       else m.f(0)))
        n_acc = m.f(p.n_cpu[w])
        n_dyn = q(n_acc * m.reuse)
        replay = q(q(q(q(n_acc * h["offchip_mem_ns"]) / h["cpu_mlp"])
                     + q(q(n_acc * q(m.reuse - 1.0)) * h["l2_hit_ns"]))
                   / h["cpu_cores"])
        present[p.cr[w]] = True
        present[p.cw[w]] = True
        dirty[p.cw[w]] = True
        pim = q(m.pim_compute(w) + m.pim_mem(w))
        serial = q(replay + q(0.75 * m.cpu_compute(w)))
        overlap = q(q(0.25 * m.cpu_compute(w)) + m.priv_mem(w))
        off = q(q(flush_b + q(n_acc * LINE)) + m.priv_fill(w))
        acc("time_ns", q(_mx(q(_mx(pim, overlap) + serial), m.bw(off))
                         + flush_ns))
        acc("offchip_bytes", off)
        acc("dram_bytes", q(off + m.pim_dram(w)))
        acc("l1_accesses", q(q(n_dyn + m.f(p.n_pim[w])) + m.priv[w]))
        acc("l2_accesses", q(n_dyn + n_flush))
        acc("flush_lines", n_flush)
        acc("blocked_accesses", n_dyn)


def _nc(m: _Model, acc):
    p, h, q = m.p, m.h, m.q
    for w in range(p.W):
        _, _, mem, fill = m.cpu_step(None, None, w, 0, cacheable=False)
        pim = q(m.pim_compute(w) + m.pim_mem(w))
        cpu = q(q(m.cpu_compute(w) + mem) + m.priv_mem(w))
        off = q(fill + m.priv_fill(w))
        acc("time_ns", _mx(_mx(pim, cpu), m.bw(off)))
        acc("offchip_bytes", off)
        acc("dram_bytes", q(q(q(fill * h["nc_dram_energy_factor"])
                              + m.priv_fill(w)) + m.pim_dram(w)))
        acc("l1_accesses", q(m.f(p.n_pim[w]) + m.priv[w]))
        acc("l2_accesses", m.f(0))


def _image(p, idx):
    bits = np.zeros(SIG_BITS, bool)
    bits[p.pos[:, idx].ravel()] = True
    return bits


def _members(p, state, bits):
    """Lines set in ``state`` whose every segment bit is set in ``bits``
    (membership with the signature's real false positives)."""
    idx = np.flatnonzero(state)
    return idx[bits[p.pos[:, idx]].all(axis=0)]


def _conflict(p, state, bits):
    """The CPUWriteSet bank of ``state`` (register = line id mod 16)
    intersects ``bits`` in every segment of some register."""
    idx = np.flatnonzero(state)
    if idx.size == 0:
        return False
    hit = bits[p.pos[:, idx]]                      # (SEGMENTS, k)
    seg = np.zeros((REGS, SEGMENTS), bool)
    for m in range(SEGMENTS):
        seg[p.reg[idx[hit[m]]], m] = True
    return bool(seg.all(axis=1).any())


def _lazypim(m: _Model, acc, cfg):
    p, h, q = m.p, m.h, m.q
    n = p.n
    partial = bool(cfg["partial_commits"])
    sig_bytes = q(2.0 * SIG_BITS / 8.0)
    dbi_interval = q(m.f(cfg["dbi_interval_cycles"]) / h["freq_ghz"])
    present, dirty = np.zeros(n, bool), np.zeros(n, bool)
    cpuws, conc = np.zeros(n, bool), np.zeros(n, bool)
    read_bm = np.zeros(n, bool)
    read_bits, write_bits = np.zeros(SIG_BITS, bool), np.zeros(SIG_BITS, bool)
    replay, dbi_t = m.f(0), m.f(0)
    for w in range(p.W):
        _pre(p, w, present, dirty)
        dirty_before = dirty.copy()
        hits, misses, mem, fill = m.cpu_step(present, dirty, w,
                                             m.cap["thread_cache_cap"])
        cw = np.zeros(n, bool)
        cw[p.cw[w]] = True
        fresh = partial or bool(p.start[w])
        cpuws = (dirty_before if fresh else cpuws) | cw
        conc = cw if fresh else conc | cw
        r_img, w_img = _image(p, p.pr[w]), _image(p, p.pw[w])
        read_bits = r_img if fresh else read_bits | r_img
        write_bits = w_img if fresh else write_bits | w_img
        r_bm = np.zeros(n, bool)
        r_bm[p.pr[w]] = True
        read_bm = r_bm if fresh else read_bm | r_bm

        pim = q(m.pim_compute(w) + m.pim_mem(w))
        cheap = q(m.pim_compute(w) + q(q(m.f(p.uniq_w[w]) * h["pim_mem_ns"])
                                       / h["pim_cores"]))
        replay = cheap if fresh else q(replay + cheap)

        commit = True if partial else bool(p.end[w])
        c1 = commit and _conflict(p, cpuws, read_bits)
        exact = commit and bool((cpuws & read_bm).any())
        c2 = _conflict(p, conc, read_bits)
        rollbacks = m.f((1 + int(c2)) if c1 else 0)
        flush = _members(p, dirty, read_bits) if c1 else np.zeros(0, np.int64)
        n_flush = q(m.f(flush.size) + q(_mx(q(rollbacks - 1.0), m.f(0))
                                        * m.f(_members(p, conc, read_bits).size)))
        dirty[flush] = False
        flush_b = q(n_flush * LINE)
        refetch = q(q(n_flush * h["pim_mem_ns"]) / h["pim_cores"])
        rb_ns = q(rollbacks * q(q(q(replay + refetch)
                                  + q(2.0 * h["offchip_msg_ns"]))
                                + m.bw(sig_bytes)))
        rb_ns = q(rb_ns + m.bw(flush_b))

        if commit:
            merge = _members(p, dirty, write_bits)
            inv = _members(p, present, write_bits)
            present[inv] = False
            dirty[merge] = False
            n_merge = m.f(merge.size)
        else:
            n_merge = m.f(0)
        attempts = q(1.0 + rollbacks) if commit else m.f(0)
        commit_b = q(q(attempts * q(sig_bytes + 2.0 * CTRL)) + q(n_merge * LINE))
        commit_ns = (q(m.f(cfg["commit_exposure"])
                       * q(q(2.0 * h["offchip_msg_ns"]) + m.bw(sig_bytes)))
                     if commit else m.f(0))

        cpu = q(q(m.cpu_compute(w) + mem) + m.priv_mem(w))
        off = q(q(q(fill + m.priv_fill(w)) + commit_b) + flush_b)
        t_w = q(q(_mx(_mx(pim, cpu), m.bw(off)) + commit_ns) + rb_ns)
        dram = q(q(q(q(fill + m.priv_fill(w)) + m.pim_dram(w)) + flush_b)
                 + q(n_merge * LINE))

        dbi_t = q(dbi_t + t_w)
        fire = bool(cfg["use_dbi"]) and dbi_t > dbi_interval
        n_dbi = 0
        if fire:
            n_dirty = int(dirty.sum())
            frac = np.float32(cfg["dbi_lines_per_fire"]) / np.float32(
                max(n_dirty, 1))
            frac = q(min(max(frac, np.float32(0)), np.float32(1)))
            idx = np.flatnonzero(dirty)
            drain = idx[_u01(p.lines[idx], w, *XXH) < frac]
            n_dbi = drain.size
            dirty[drain] = False
            dbi_t = m.f(0)
        n_dbi = m.f(n_dbi)
        off = q(off + q(n_dbi * LINE))
        dram = q(dram + q(n_dbi * LINE))

        acc("time_ns", t_w)
        acc("offchip_bytes", off)
        acc("dram_bytes", dram)
        acc("l1_accesses", _l1(m, w))
        acc("l2_accesses", q(q(q(misses + hits) + n_flush) + n_dbi))
        acc("commits", m.f(1 if commit else 0))
        acc("conflicts_sig", m.f(1 if c1 else 0))
        acc("conflicts_exact", m.f(1 if exact else 0))
        acc("rollbacks", rollbacks)
        acc("flush_lines", n_flush)
        acc("dbi_writebacks", n_dbi)
        acc("sig_bytes", q(attempts * sig_bytes))
        if commit:
            read_bits = np.zeros(SIG_BITS, bool)
            write_bits = np.zeros(SIG_BITS, bool)
            read_bm = np.zeros(n, bool)
            conc = np.zeros(n, bool)
            cpuws = np.zeros(n, bool)
            replay = m.f(0)


def simulate(p: Prepared, hw: dict | None = None, mechanism: str = "lazypim",
             lazy: dict | None = None, precision: str = "float32") -> dict:
    """One mechanism over one prepared trace: the result fields as floats."""
    q = rounding(precision)
    model = _Model(p, {**HW_DEFAULTS, **(hw or {})}, q)
    sums = {k: np.float32(0) for k in FIELDS}

    def acc(key, x):
        sums[key] = q(sums[key] + x)

    if mechanism == "lazypim":
        _lazypim(model, acc, {**LAZY_DEFAULTS, **(lazy or {})})
    else:
        {"cpu": _cpu, "ideal": _ideal, "fg": _fg, "cg": _cg,
         "nc": _nc}[mechanism](model, acc)
    return {k: float(v) for k, v in sums.items()}
