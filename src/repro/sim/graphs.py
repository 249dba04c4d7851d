"""Synthetic input datasets shaped like the paper's (§6.1).

The paper uses three SNAP graphs and an in-house HTAP IMDB.  We have no
network access, so we regenerate inputs with *matched* node/edge counts and a
power-law degree distribution (all three SNAP graphs are heavy-tailed), and an
IMDB with the paper's exact table geometry (64 tables x 64 K tuples x 32
fields, uniform random integers).

Beside the SNAP-shaped inputs there are *generated* graphs
(:data:`GENERATED_GRAPHS`): built from keys of their own rather than from
the study seed, so one graph serves every study that names the same keys.
Today that is Graph500's Kronecker graph (:func:`kronecker_edges`).

Every graph is built once per (name, seed, scale, keys) in this process,
inside the ``repro:graph`` span, and its edge array is kept on the device
for synthesis; a host copy is made only where a caller reads it.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import spans

# Paper §6.1 dataset shapes.
GRAPH_SHAPES = {
    "enron": dict(nodes=73384, edges=367662),
    "arxiv": dict(nodes=10484, edges=28984),
    "gnutella": dict(nodes=45374, edges=109410),
}

IMDB_SHAPE = dict(tables=64, tuples_per_table=65536, fields_per_tuple=32)

# Bytes per element of the Ligra-style vertex/edge arrays.
VERTEX_VALUE_BYTES = 8  # double p_curr / p_next
EDGE_BYTES = 8          # (dst id + weight packed), Ligra CSR payload
TUPLE_FIELD_BYTES = 8   # uniformly-distributed integers (§6.1)


@dataclasses.dataclass(frozen=True)
class Graph:
    name: str
    num_nodes: int
    edges: jax.Array  # (E, 2) int32 (src, dst), sorted by src, on the device

    @property
    def num_edges(self) -> int:
        return int(self.edges.shape[0])


def make_graph(name: str, seed: int = 0, scale: float = 1.0, **keys) -> Graph:
    """The graph input ``name``: a SNAP-shaped graph (:data:`GRAPH_SHAPES`)
    drawn from the study ``seed``, or a generated graph
    (:data:`GENERATED_GRAPHS`) built from its own ``keys``, which leaves
    ``seed`` aside.  A SNAP-shaped graph takes no keys: one given raises a
    ``TypeError`` naming it.  Memoized either way; the edges are read-only.
    """
    if name in GENERATED_GRAPHS:
        return _generated(name, float(scale), tuple(sorted(keys.items())))
    if keys:
        raise TypeError(f"graph {name!r} takes no keys {sorted(keys)}")
    return _snap_graph(name, seed, scale)


def graph_keys(name: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(every key, the required keys) the graph input ``name`` takes; both
    empty for a SNAP-shaped graph."""
    if name not in GENERATED_GRAPHS:
        return (), ()
    params = [p for p in inspect.signature(GENERATED_GRAPHS[name]).parameters
              .values() if p.kind is p.KEYWORD_ONLY]
    return (tuple(p.name for p in params),
            tuple(p.name for p in params if p.default is p.empty))


@functools.lru_cache(maxsize=32)
def _snap_graph(name: str, seed: int, scale: float) -> Graph:
    """Power-law graph with the paper dataset's node/edge counts.

    ``scale`` < 1 shrinks the graph proportionally (used by fast tests).
    Graphs are *inputs* (like the SNAP files) and treated as read-only, so
    the constructor is memoized — several workload families (and both
    synthesis backends) share one instance per (name, seed, scale).
    """
    shape = GRAPH_SHAPES[name]
    n = max(16, int(shape["nodes"] * scale))
    e = max(32, int(shape["edges"] * scale))
    with spans.span("graph", graph=name, edges=e):
        rng = np.random.default_rng(seed ^ zlib.crc32(name.encode()) & 0xFFFF)
        # Zipf-ish endpoint sampling: heavy-tailed in-degree like SNAP's.
        ranks = np.arange(1, n + 1, dtype=np.float64)
        probs = ranks ** -0.9
        probs /= probs.sum()
        dst = rng.choice(n, size=e, p=probs).astype(np.int32)
        src = rng.integers(0, n, size=e).astype(np.int32)
        # permute vertex ids so hot vertices are scattered in the address space
        perm = rng.permutation(n).astype(np.int32)
        edges = np.stack([perm[src], perm[dst]], axis=1)
        # sort by source: Ligra CSR edge arrays are contiguous per src
        edges = edges[np.argsort(edges[:, 0], kind="stable")]
        return Graph(name=name, num_nodes=n, edges=spans.h2d(edges))


@functools.lru_cache(maxsize=8)
def _generated(name: str, scale: float, keys: tuple) -> Graph:
    return GENERATED_GRAPHS[name](scale, **dict(keys))


# ---------------------------------------------------------------------------
# Graph500 Kronecker graph
# ---------------------------------------------------------------------------

# Graph500 v3.0's initiator A, B, C (D = 1 - A - B - C = 0.05).
KRONECKER_INITIATOR = (0.57, 0.19, 0.19)


def fmix32(h):
    """MurmurHash3's 32-bit finalizer on a uint32 array (numpy or JAX): a
    bijection of uint32, so one key never draws the same value twice."""
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def kronecker_thresholds() -> tuple[int, int, int]:
    """The spec's three probabilities as uint32 thresholds ``floor(p *
    2**32)``: ``A + B`` for the i bit, then ``C / (1 - A - B)`` after an i
    bit of 1 and ``A / (A + B)`` after a 0 for the j bit.  A draw ``u``
    sets its bit where ``u > threshold``."""
    a, b, c = KRONECKER_INITIATOR
    ab = a + b
    return tuple(int(p * 2**32) for p in (ab, c / (1 - ab), a / ab))


def kronecker_keys(graph_seed: int, levels: int) -> np.ndarray:
    """(2 * levels + 1,) uint32 stream keys: the i and j draws of each level
    in turn, then the vertex relabelling."""
    names = [f"{s}/{ib}" for ib in range(levels) for s in ("i", "j")]
    return np.asarray([zlib.crc32(f"kronecker/{graph_seed}/{n}".encode())
                       for n in names + ["perm"]], np.uint32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kronecker_device(levels: int, edge_factor: int, keys):
    n, m = 1 << levels, edge_factor << levels
    t_ab, t_c, t_a = (np.uint32(t) for t in kronecker_thresholds())
    e = jnp.arange(m, dtype=jnp.uint32)
    i = j = jnp.zeros((m,), jnp.int32)
    for ib in range(levels):
        ii = fmix32(keys[2 * ib] ^ e) > t_ab
        jj = fmix32(keys[2 * ib + 1] ^ e) > jnp.where(ii, t_c, t_a)
        i = i | (ii.astype(jnp.int32) << ib)
        j = j | (jj.astype(jnp.int32) << ib)
    label = jnp.argsort(fmix32(keys[-1] ^ jnp.arange(n, dtype=jnp.uint32)))
    u, v = label[i].astype(jnp.int32), label[j].astype(jnp.int32)
    src, dst = jax.lax.sort((jnp.concatenate([u, v]), jnp.concatenate([v, u])),
                            num_keys=2)
    return jnp.stack([src, dst], axis=1)


def kronecker_edges(scale: float = 1.0, *, kron_scale: int,
                    edge_factor: int = 16, graph_seed: int = 0) -> Graph:
    """Graph500's Kronecker graph (specification v3.0, ``kronecker_generator``)
    at SCALE ``kron_scale``, built on the device by one jitted program.

    N = 2**S vertices and M = ``edge_factor`` * N edges.  For each level
    ``ib`` in 0..S-1 every edge draws its i bit (``u > A + B``) and then its
    j bit (``v > C / (1 - A - B)`` after an i bit of 1, else ``v > A / (A +
    B)``) and adds it at bit ``ib`` of its endpoints.  Vertex ``x`` is then
    relabelled ``argsort(fmix32(key ^ arange(N)))[x]``.  A ``scale`` below 1
    lowers S by ``round(-log2(scale))`` (the tests' small sizes).

    Departures from the specification, for a trace that both this program
    and a plain reference regenerate bit for bit:

    * the random stream is a counter hash, ``fmix32(key ^ edge index)`` with
      one key per (``graph_seed``, stream, level), compared in integers with
      ``floor(p * 2**32)``, not the spec's floating-point ``rand``;
    * the undirected graph is stored as CSR: every edge in both directions
      (2M entries), duplicates and self-loops kept, sorted by (src, dst),
      so the spec's edge shuffle, which only orders the edge list, is left
      out;
    * the frontier families (``bfs``, ``sssp``) start each search level at
      a random CSR entry, so roots are degree-weighted rather than uniform
      over the vertices of degree 1 or more, and SSSP weights are not
      materialised (an edge line holds destination + weight in 8 bytes).
    """
    levels = kron_scale - (round(-math.log2(scale)) if scale < 1 else 0)
    m = edge_factor << max(levels, 0)
    if scale > 1 or levels < 1 or not 1 <= m < 2**30:
        # 2M CSR entries must index in int32
        raise ValueError(f"kronecker: SCALE {kron_scale} at scale {scale} "
                         f"with edge factor {edge_factor} is out of range")
    with spans.span("graph", graph="kronecker", edges=2 * m):
        keys = spans.h2d(kronecker_keys(graph_seed, levels))
        edges = _kronecker_device(levels, edge_factor, keys)
        return Graph(name="kronecker", num_nodes=1 << levels, edges=edges)


# Generated graph inputs: name -> make(scale, **keys) -> Graph.
GENERATED_GRAPHS = {"kronecker": kronecker_edges}


@dataclasses.dataclass(frozen=True)
class GraphLayout:
    """Cache-line layout of the PIM data region for a graph app.

    Region order (line granularity): [p_curr | p_next | frontier | edges].
    Matches Listing 1: ``@PIM double* p_curr, p_next; @PIM bool* frontier``
    plus the shared CSR edge array of the ``@PIM Graph``.
    """

    num_nodes: int
    num_edges: int
    vertex_lines: int
    frontier_lines: int
    edge_lines: int

    @property
    def p_curr_base(self) -> int:
        return 0

    @property
    def p_next_base(self) -> int:
        return self.vertex_lines

    @property
    def frontier_base(self) -> int:
        return 2 * self.vertex_lines

    @property
    def edge_base(self) -> int:
        return 2 * self.vertex_lines + self.frontier_lines

    @property
    def total_lines(self) -> int:
        return self.edge_base + self.edge_lines

    def vertex_line(self, base: int, vertex_ids: np.ndarray) -> np.ndarray:
        per_line = 64 // VERTEX_VALUE_BYTES
        return base + vertex_ids // per_line

    def frontier_line(self, vertex_ids: np.ndarray) -> np.ndarray:
        return self.frontier_base + vertex_ids // 64  # 1 B per flag

    def edge_line(self, edge_ids: np.ndarray) -> np.ndarray:
        per_line = 64 // EDGE_BYTES
        return self.edge_base + edge_ids // per_line


def layout_for_graph(g: Graph) -> GraphLayout:
    per_line_v = 64 // VERTEX_VALUE_BYTES
    per_line_e = 64 // EDGE_BYTES
    return GraphLayout(
        num_nodes=g.num_nodes,
        num_edges=g.num_edges,
        vertex_lines=-(-g.num_nodes // per_line_v),
        frontier_lines=-(-g.num_nodes // 64),
        edge_lines=-(-g.num_edges // per_line_e),
    )


@dataclasses.dataclass(frozen=True)
class MTLayout:
    """Cache-line layout of a *shared* PIM data region hosting two tenant
    applications: each tenant gets private ``p_curr | p_next | frontier``
    arrays, and both share one CSR edge array.

    Region order: [A.p_curr | A.p_next | A.frontier |
                   B.p_curr | B.p_next | B.frontier | edges].
    """

    vertex_lines: int
    frontier_lines: int
    edge_lines: int

    @property
    def a_pc(self) -> int:
        return 0

    @property
    def a_pn(self) -> int:
        return self.vertex_lines

    @property
    def a_fr(self) -> int:
        return 2 * self.vertex_lines

    @property
    def tenant_lines(self) -> int:
        return 2 * self.vertex_lines + self.frontier_lines

    @property
    def b_pc(self) -> int:
        return self.tenant_lines

    @property
    def b_pn(self) -> int:
        return self.tenant_lines + self.vertex_lines

    @property
    def b_fr(self) -> int:
        return self.tenant_lines + 2 * self.vertex_lines

    @property
    def edge_base(self) -> int:
        return 2 * self.tenant_lines

    @property
    def total_lines(self) -> int:
        return self.edge_base + self.edge_lines


def mt_layout_for_graph(g: Graph) -> MTLayout:
    one = layout_for_graph(g)
    return MTLayout(vertex_lines=one.vertex_lines,
                    frontier_lines=one.frontier_lines,
                    edge_lines=one.edge_lines)


@dataclasses.dataclass(frozen=True)
class IMDBLayout:
    """Line layout of the in-memory database region (§6.1): 64 tables of 64 K
    tuples x 32 8-byte fields; plus a hash-join scratch area."""

    tables: int
    tuples_per_table: int
    fields_per_tuple: int
    scale: float = 1.0

    @property
    def tuple_lines(self) -> int:
        return (self.fields_per_tuple * TUPLE_FIELD_BYTES) // 64  # 4 lines

    @property
    def table_lines(self) -> int:
        return int(self.tuples_per_table * self.scale) * self.tuple_lines

    @property
    def hash_area_lines(self) -> int:
        return max(64, self.table_lines // 4)

    @property
    def total_lines(self) -> int:
        return self.tables * self.table_lines + self.hash_area_lines

    def tuple_line(self, table: np.ndarray, tup: np.ndarray, field_line: np.ndarray):
        return table * self.table_lines + tup * self.tuple_lines + field_line

    @property
    def hash_base(self) -> int:
        return self.tables * self.table_lines


def make_imdb_layout(scale: float = 1.0) -> IMDBLayout:
    return IMDBLayout(
        tables=IMDB_SHAPE["tables"],
        tuples_per_table=IMDB_SHAPE["tuples_per_table"],
        fields_per_tuple=IMDB_SHAPE["fields_per_tuple"],
        scale=scale,
    )
