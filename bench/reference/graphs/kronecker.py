"""Graph500's Kronecker graph (specification v3.0, graph500.org:
``kronecker_generator``), in plain numpy; it imports nothing of the program.

Workload keys: ``kron_scale`` (SCALE, S), ``edge_factor`` (16 in the
specification) and ``graph_seed``.  The study seed is left aside: one graph
per set of keys, built once per process and searched by every study.

N = 2**S vertices, M = edge_factor * N edges.  Initiator A, B, C = 0.57,
0.19, 0.19 (D = 0.05).  For each level ``ib`` in 0..S-1, edge ``e`` draws

    ii = h_i(e) > floor((A + B) * 2**32)
    jj = h_j(e) > floor((C / (1 - (A + B)) if ii else A / (A + B)) * 2**32)

and adds ``ii`` to its source and ``jj`` to its target at bit ``ib``; here
``h(e) = fmix32(key ^ e)`` (MurmurHash3's finalizer) with the key the CRC-32
of ``"kronecker/<graph_seed>/<i|j>/<ib>"``.  Vertex ``x`` becomes
``argsort(fmix32(key ^ arange(N)))[x]``, the key that of
``"kronecker/<graph_seed>/perm"``.  The undirected graph is returned as CSR
entries: every edge in both directions, duplicates and self-loops kept,
sorted by (source, target); the specification's edge shuffle only orders
the edge list and is left out.

A ``scale`` below 1 lowers S by ``round(-log2(scale))`` (the tests' small
sizes).
"""

from __future__ import annotations

import functools
import math
import zlib

import numpy as np

A, B, C = 0.57, 0.19, 0.19


def _fmix32(h):
    """MurmurHash3's 32-bit finalizer, in place on a uint32 array."""
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def _key(graph_seed, name):
    return np.uint32(zlib.crc32(f"kronecker/{graph_seed}/{name}".encode()))


def _threshold(p):
    return np.uint32(int(p * 2**32))


@functools.lru_cache(maxsize=2)
def _build(levels, edge_factor, graph_seed):
    n, m = 2**levels, edge_factor * 2**levels
    t_i = _threshold(A + B)
    t_j1, t_j0 = _threshold(C / (1 - (A + B))), _threshold(A / (A + B))
    e = np.arange(m, dtype=np.uint32)
    i = np.zeros(m, np.int32)
    j = np.zeros(m, np.int32)
    h = np.empty(m, np.uint32)
    for ib in range(levels):
        np.bitwise_xor(e, _key(graph_seed, f"i/{ib}"), out=h)
        ii = _fmix32(h) > t_i
        np.bitwise_xor(e, _key(graph_seed, f"j/{ib}"), out=h)
        jj = _fmix32(h) > np.where(ii, t_j1, t_j0)
        i |= ii.astype(np.int32) << ib
        j |= jj.astype(np.int32) << ib
    del e, h
    perm = np.arange(n, dtype=np.uint32) ^ _key(graph_seed, "perm")
    label = np.argsort(_fmix32(perm)).astype(np.int32)
    u, v = label[i], label[j]
    del i, j
    pairs = np.concatenate([u, v]).astype(np.int64) << 32
    pairs |= np.concatenate([v, u]).astype(np.int64)
    del u, v
    pairs.sort()
    edges = np.empty((2 * m, 2), np.int32)
    edges[:, 0] = pairs >> 32
    edges[:, 1] = pairs & 0xFFFFFFFF
    edges.setflags(write=False)
    return n, edges


def make_graph(seed, scale, *, kron_scale, edge_factor=16, graph_seed=0):
    """``(num_nodes, edges)``: edges an (2M, 2) int32 array sorted by source
    (then target)."""
    levels = kron_scale - (round(-math.log2(scale)) if scale < 1 else 0)
    m = edge_factor * 2 ** max(levels, 0)
    if scale > 1 or levels < 1 or not 1 <= m < 2**30:
        raise ValueError(f"kronecker: SCALE {kron_scale} at scale {scale} "
                         f"with edge factor {edge_factor} is out of range")
    return _build(levels, edge_factor, graph_seed)
