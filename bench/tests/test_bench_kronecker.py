"""The reference's Kronecker graph (``reference/graphs/kronecker.py``) and
the program's agree bit for bit, and so does every graph family's trace on
it; the reference module imports nothing of the program."""

import ast
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from reference import synth as RS  # noqa: E402
from reference.graphs import kronecker as RK  # noqa: E402

KRON = dict(edge_factor=16, graph_seed=1)


@pytest.mark.parametrize("kron_scale", [10, 14])
def test_the_program_builds_the_reference_graph(kron_scale):
    from repro.sim import graphs as G

    g = G.make_graph("kronecker", seed=3, kron_scale=kron_scale, **KRON)
    n, edges = RS.make_graph("kronecker", 4, 1.0, kron_scale=kron_scale, **KRON)
    assert g.num_nodes == n == 2**kron_scale
    assert np.array_equal(np.asarray(g.edges), edges)


def test_a_small_scale_lowers_the_level_count_alike():
    from repro.sim import graphs as G

    g = G.make_graph("kronecker", scale=0.1, kron_scale=13, **KRON)
    n, edges = RK.make_graph(0, 0.1, kron_scale=13, **KRON)
    assert g.num_nodes == n == 2**10  # 13 - round(3.32)
    assert np.array_equal(np.asarray(g.edges), edges)
    for bad in (dict(scale=2.0), dict(scale=1e-6)):
        with pytest.raises(ValueError, match="out of range"):
            RK.make_graph(0, kron_scale=13, **KRON, **bad)
        with pytest.raises(ValueError, match="out of range"):
            G.make_graph("kronecker", kron_scale=13, **KRON, **bad)


@pytest.mark.parametrize("app", ["bfs", "sssp", "mtmix"])
def test_every_trace_field_matches_the_reference(app):
    from repro.sim.trace import make_trace

    kw = dict(KRON, kron_scale=14, seed=2**31 + 11, threads=16,
              num_kernels=6, windows_per_kernel=3)
    prog = make_trace(app, "kronecker", **kw)
    ref = RS.make_trace(app, "kronecker", **kw)
    assert prog.name == ref["name"] == f"{app}-kronecker"
    assert prog.num_lines == ref["num_lines"]
    for f in ("pim_reads", "pim_writes", "cpu_reads", "cpu_writes",
              "kernel_id", "kernel_start", "kernel_end", "pim_instr",
              "cpu_instr"):
        assert np.array_equal(np.asarray(getattr(prog, f)), ref[f]), f
    assert np.array_equal(np.asarray(prog.cpu_priv_accesses), ref["cpu_priv"])
    pre = np.asarray(prog.pre_writes)
    for k, lines in enumerate(ref["pre_lines"]):
        assert np.array_equal(np.flatnonzero(pre[k]), lines), k


def test_the_reference_graph_imports_nothing_of_the_program():
    tree = ast.parse((BENCH / "reference" / "graphs" / "kronecker.py")
                     .read_text())
    mods = {a.name.split(".")[0] for n in ast.walk(tree)
            if isinstance(n, ast.Import) for a in n.names}
    mods |= {n.module.split(".")[0] for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n.module}
    assert mods <= {"__future__", "functools", "math", "zlib", "numpy"}
