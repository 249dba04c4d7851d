"""Graph500's Kronecker graph as a generated graph input: its shape at a
small SCALE, one build per process inside ``repro:graph`` with its bytes
counted, no edge bytes put by a warm synthesis, the keys a study admits,
and the SNAP-shaped inputs and fleets left as they were."""

import math

import numpy as np
import pytest

from repro.api import Study, workload
from repro.runtime import spans
from repro.sim import graphs as G
from repro.sim.trace import (GENERATED_GRAPHS, GRAPH_INPUTS, all_workloads,
                             make_trace)
from test_spans import fake  # noqa: F401  (the fake profiler annotation)

KRON = dict(kron_scale=14, edge_factor=16, graph_seed=1)
SMALL = dict(num_kernels=4, windows_per_kernel=2)


def _opened(fake, name):
    return [a for a in fake.opened if a.name == "repro:" + name]


@pytest.fixture(scope="module")
def s14():
    g = G.make_graph("kronecker", **KRON)
    return g, np.asarray(g.edges)


def test_the_csr_is_symmetric_sorted_and_twice_the_edges(s14):
    g, e = s14
    n, m = 2**14, 16 * 2**14
    assert g.num_nodes == n and e.shape == (2 * m, 2) and e.dtype == np.int32
    assert e.min() >= 0 and e.max() < n
    fwd = e[:, 0].astype(np.int64) << 32 | e[:, 1]
    rev = e[:, 1].astype(np.int64) << 32 | e[:, 0]
    assert np.all(np.diff(fwd) >= 0)
    assert np.array_equal(fwd, np.sort(rev))


def test_the_degrees_are_heavy_tailed_with_graph500_isolated_share(s14):
    """Vertex v with k one-bits of S has expected degree 2 * 16 * 1.52**(S-k)
    * 0.48**k, so about sum_k C(S, k) / 2**S * exp(-degree) of the vertices
    are isolated: 23.5 % at S = 14 (40.7 % at S = 21)."""
    g, e = s14
    deg = np.bincount(e[:, 0], minlength=g.num_nodes)
    assert deg.mean() == 32 and deg.max() > 100 * deg.mean()
    S = 14
    want = sum(math.comb(S, k) / 2**S * math.exp(-32 * 1.52**(S - k) * 0.48**k)
               for k in range(S + 1))
    assert abs((deg == 0).mean() - want) < 0.02


def test_the_graph_ignores_the_study_seed_and_follows_its_own(s14):
    g, e = s14
    assert G.make_graph("kronecker", seed=12345, **KRON) is g
    other = G.make_graph("kronecker", **dict(KRON, graph_seed=2))
    assert not np.array_equal(np.asarray(other.edges), e)
    small = G.make_graph("kronecker", scale=0.01, **dict(KRON, kron_scale=21))
    assert np.array_equal(np.asarray(small.edges), e)  # 21 - round(6.64)


@pytest.mark.parametrize("app", ["bfs", "sssp", "mtmix", "pagerank"])
def test_the_families_run_on_it_bit_identical_to_the_numpy_backend(app):
    kw = dict(KRON, seed=2**31 + 7, **SMALL)
    jt = make_trace(app, "kronecker", **kw)
    rt = make_trace(app, "kronecker", backend="ref", **kw)
    assert jt.name == rt.name == f"{app}-kronecker"
    for f in ("pim_reads", "pim_writes", "cpu_reads", "cpu_writes",
              "pre_writes", "pim_instr", "cpu_instr"):
        np.testing.assert_array_equal(np.asarray(getattr(jt, f)),
                                      np.asarray(getattr(rt, f)), err_msg=f)


@pytest.mark.parametrize("app,graph", [
    ("bfs", "arxiv"), ("pagerank", "enron"), ("mtmix", "gnutella"),
    ("htap128", None), ("htap_stream", None)])
def test_a_built_in_graph_or_table_family_refuses_a_graph_key(app, graph):
    with pytest.raises(TypeError, match="graph_seed"):
        make_trace(app, graph, graph_seed=3, scale=0.01, **SMALL)


def test_the_graph_is_built_once_inside_its_span_with_its_bytes(fake):
    kw = dict(KRON, kron_scale=10, graph_seed=987654321)
    g = G.make_graph("kronecker", **kw)
    G.make_graph("kronecker", seed=5, **kw)
    make_trace("bfs", "kronecker", **kw, **SMALL)
    (sp,) = _opened(fake, "graph")
    # born on the device: only the (2 S + 1) stream keys are put
    assert sp.meta == {"graph": "kronecker", "edges": g.num_edges,
                       "h2d_bytes": (2 * 10 + 1) * 4, "d2h_bytes": 0}
    # a SNAP-shaped graph is drawn on the host and put once, whole
    fake.opened.clear()
    snap = G.make_graph("arxiv", seed=987654321, scale=0.05)
    G.make_graph("arxiv", seed=987654321, scale=0.05)
    (sp,) = _opened(fake, "graph")
    assert sp.meta["h2d_bytes"] == snap.edges.nbytes and sp.meta["d2h_bytes"] == 0
    # the numpy backend reads the edges to the host, counted once
    fake.opened.clear()
    make_trace("bfs", "kronecker", backend="ref", **kw, **SMALL)
    (synth,) = _opened(fake, "synth")
    assert synth.meta["d2h_bytes"] == g.edges.nbytes
    assert _opened(fake, "graph") == []


def test_a_warm_synthesis_puts_no_edge_bytes(fake):
    kw = dict(KRON, kron_scale=12, **SMALL)
    make_trace("sssp", "kronecker", seed=1, **kw)
    fake.opened.clear()
    n = len(spans.recorded())
    make_trace("sssp", "kronecker", seed=2, **kw)
    make_trace("bfs", "kronecker", seed=3, **kw)
    assert [a.name for a in fake.opened] == ["repro:synth", "repro:synth"]
    assert all(a.meta["h2d_bytes"] == 0 and a.meta["d2h_bytes"] == 0
               for a in fake.opened)
    assert all(p[3]["h2d_bytes"] == 0 for p in spans.recorded()[n:])


def test_a_study_admits_the_graph_keys_and_refuses_others():
    st = Study(workloads=[workload("bfs", "kronecker", **KRON, **SMALL)],
               mechanisms=("cpu", "lazypim"))
    assert st.num_points == 1
    bad = [(workload("bfs", "kronecker", **dict(KRON, colour=1)), "colour"),
           (workload("bfs", "kronecker", edge_factor=16), "kron_scale"),
           (workload("bfs", "arxiv", graph_seed=1), "graph_seed"),
           (workload("htap128", kron_scale=21), "kron_scale"),
           ("bfs-kronecker", "kron_scale"),
           (workload("bfs", "kroneker", **KRON), "kroneker")]
    for entry, named in bad:
        with pytest.raises(ValueError, match=named):
            Study(workloads=[entry])


def test_the_fleets_and_graph_inputs_leave_generated_graphs_out():
    assert GRAPH_INPUTS == ("enron", "arxiv", "gnutella")
    assert GENERATED_GRAPHS == ("kronecker",)
    for extended in (False, True):
        assert all(g != "kronecker" for _, g in all_workloads(extended))
    assert len(all_workloads()) == 12 and len(all_workloads(True)) == 22
