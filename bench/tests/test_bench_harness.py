"""CPU rehearsal of the benchmark harness at tiny sizes: the files it finds
by name, the contract of its result line, its refusal of the CPU backend,
its accounting of real work, and that a new cell, a new workload family or
a new graph input needs only new files."""

import json
import pathlib
import re
import shutil
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402
import traffic  # noqa: E402
from reference import synth as RS  # noqa: E402

TINY = dict(num_kernels=2, windows_per_kernel=2, scale=0.01)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_benchmark_json_has_the_contract_keys_and_names(bench):
    assert set(bench) == TOP_KEYS
    assert bench["command"][1:] == ["bench/run.py"] and bench["paths"] == ["bench"]
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        e2e = {m["name"] for m in harness.cell_metrics(bench, w, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(bench, w, "per_layer")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    assert len(json.dumps(bench)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.load_benchmark(ROOT)["workloads"]])
def test_every_config_and_mix_builds_at_a_tiny_size(bench, cell):
    from repro.sim.trace import make_trace

    c = harness.find_cell(bench, cell)
    spec = traffic.study_spec(harness.load_config(bench, c, ROOT),
                              harness.load_mix(c, ROOT), 7, 0, TINY)
    study = harness.make_study(spec)
    assert study.num_points == len(spec["workloads"]) * len(
        traffic.hw_points(spec))
    for w in spec["workloads"]:
        kw = {k: v for k, v in w.items() if k not in ("app", "graph")}
        _same_trace(make_trace(w["app"], w.get("graph"), **kw),
                    RS.make_trace(w["app"], w.get("graph"), **kw))


def _same_trace(prog, ref):
    assert prog.name == ref["name"] and prog.num_lines == ref["num_lines"]
    for f in ("pim_reads", "pim_writes", "cpu_reads", "cpu_writes"):
        assert np.array_equal(np.asarray(getattr(prog, f)), ref[f]), f


@pytest.mark.parametrize("app,graph", [
    ("pagerank", "enron"), ("radii", "arxiv"), ("components", "gnutella"),
    ("bfs", "arxiv"), ("sssp", "enron"), ("htap_stream", None),
    ("mtmix", "gnutella")])
def test_the_reference_synthesis_matches_every_other_family(app, graph):
    """The built-in families no cell runs yet, so that a later configuration
    can name them in a data file alone (a new family is a new file:
    ``test_a_new_family_and_graph_input_need_only_new_files``)."""
    from repro.sim.trace import make_trace

    kw = dict(TINY, seed=2**31 + 5, threads=16)
    _same_trace(make_trace(app, graph, **kw), RS.make_trace(app, graph, **kw))


TOY_APP = """\
from reference import synth as RS


def make_trace(app, graph, *, seed, threads, num_kernels, windows_per_kernel,
               scale, cpu_reuse, flavour):
    tr = RS.make_trace(flavour, graph, seed=seed, threads=threads,
                       num_kernels=num_kernels,
                       windows_per_kernel=windows_per_kernel, scale=scale,
                       cpu_reuse=cpu_reuse)
    return {**tr, "name": f"{app}-{graph}"}
"""

TOY_GRAPH = """\
from reference import synth as RS


def make_graph(seed, scale, *, like):
    return RS.make_graph(like, seed, scale)
"""


def _same_fields(a, b):
    assert a.keys() == b.keys()
    for k in a.keys() - {"name"}:
        x, y = a[k], b[k]
        if isinstance(x, list):
            assert len(x) == len(y), k
            assert all(np.array_equal(p, q) for p, q in zip(x, y)), k
        else:
            assert np.asarray(x).dtype == np.asarray(y).dtype, k
            assert np.array_equal(x, y), k


def test_a_new_family_and_graph_input_need_only_new_files(tmp_path,
                                                          monkeypatch):
    """A workload family and a graph input, each one new file beside a copy
    of the reference, with a key of its own passed through: the reference
    finds both, runs a built-in family on the new graph as on arxiv's, and
    no file that was there changed.  (The families key their random
    streams by the graph's name, so bfs on the new graph is compared with
    bfs on arxiv's graph under the new name.)"""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    ref = tmp_path / "bench" / "reference"
    (ref / "apps").mkdir()
    (ref / "graphs").mkdir()
    (ref / "apps" / "toyfam.py").write_text(TOY_APP)
    (ref / "graphs" / "toygraph.py").write_text(TOY_GRAPH)
    monkeypatch.syspath_prepend(str(tmp_path / "bench"))
    for mod in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, mod)
    from reference import synth as copy

    assert pathlib.Path(copy.__file__).parent == ref
    kw = dict(TINY, seed=2**31 + 9, threads=16)
    n, edges = copy.make_graph("toygraph", kw["seed"], 1.0, like="arxiv")
    want_n, want_edges = RS.make_graph("arxiv", kw["seed"], 1.0)
    assert n == want_n and np.array_equal(edges, want_edges)
    got = copy.make_trace("bfs", "toygraph", like="arxiv", **kw)
    with monkeypatch.context() as m:
        m.setattr(copy, "make_graph",
                  lambda name, seed, scale: RS.make_graph("arxiv", seed, scale))
        want = copy.make_trace("bfs", "toygraph", **kw)
    assert got["name"] == want["name"] == "bfs-toygraph"
    _same_fields(got, want)
    fam = copy.make_trace("toyfam", "arxiv", flavour="sssp", **kw)
    assert fam["name"] == "toyfam-arxiv"
    _same_fields(fam, RS.make_trace("sssp", "arxiv", **kw))
    with pytest.raises(ValueError, match="toyfam"):
        RS.make_trace("toyfam", "arxiv", flavour="sssp", **kw)
    with pytest.raises(TypeError):
        copy.make_trace("bfs", "toygraph", like="arxiv", colour=1, **kw)
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("app,graph", [
    ("htap128", None), ("htap_stream", None), ("bfs", "arxiv"),
    ("pagerank", "enron"), ("mtmix", "gnutella")])
def test_a_built_in_family_refuses_a_key_it_does_not_take(app, graph):
    with pytest.raises(TypeError, match="graph_seed"):
        RS.make_trace(app, graph, graph_seed=3, **TINY)


def test_study_seeds_are_fixed_by_the_run_seed_and_fit_31_bits():
    big = 2**31 + 12345
    assert traffic.study_seed(big, 3) == traffic.study_seed(big, 3)
    seeds = {traffic.study_seed(big, i) for i in range(-1, 50)}
    assert len(seeds) == 51 and all(0 <= s < 2**31 for s in seeds)


def test_the_harness_refuses_the_cpu_backend(capsys):
    with pytest.raises(harness.NoDevice):
        harness.run_cell("large-bwsweep", 1, 1.0, False, t_start=0.0,
                         cache=False)
    rc = harness.main(["--workload", "htap-fig7", "--seed", "1",
                       "--seconds", "1"], t_start=0.0)
    assert rc != 0 and capsys.readouterr().out == ""


@pytest.mark.parametrize("trace", [False, True])
def test_the_last_line_has_exactly_the_contract_keys(bench, trace):
    out = harness.run_cell("large-bwsweep", 2**31 + 99, 0.3, trace,
                           t_start=0.0, platform=None, workload_kw=TINY,
                           cache=False)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(out) == keys + ["checks"]
    assert out["correct"] is True and out["failed"] == 0
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(out["device"]) == dev | ({"busy_s", "window_s"} if trace else set())
    cell = harness.find_cell(bench, "large-bwsweep")
    if trace:
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "host.prep_s" in out["metrics"]
        want = {m["name"] for m in harness.cell_metrics(bench, cell, "per_layer")}
    else:
        want = {m["name"] for m in harness.cell_metrics(bench, cell, "end_to_end")}
    assert set(out["metrics"]) <= want
    assert trace or set(out["metrics"]) == want
    json.dumps(out)


def test_real_window_accounting_leaves_out_pad_lanes_and_windows():
    base = dict(graph="arxiv", scale=0.01, seed=3)
    spec = dict(workloads=[dict(app="pagerank", num_kernels=2,
                                windows_per_kernel=2, **base),
                           dict(app="bfs", num_kernels=3,
                                windows_per_kernel=3, **base)],
                hw={}, hw_grid={"offchip_bw_gbs": [16.0, 32.0]},
                mechanisms=["cpu", "lazypim"], lazy={}, threads=16)
    rec = harness.StudyRecord(0, 3)
    harness.run_study(spec, rec, harness.Spans(False), 1)
    plan = harness.make_study(spec).plan(devices=1)
    (bucket,) = plan.buckets
    assert bucket["num_windows"] == 9 and bucket["lanes"] == 4
    assert rec.real_lane_windows == (4 + 9) * 2  # not 9 windows x 4 lanes
    assert rec.work == rec.real_lane_windows * 2
    assert rec.padded_line_lanes > rec.real_line_lanes


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, each a
    new file, plus new BENCHMARK.json entries: the harness runs the cell
    and reports the metric with no existing file edited."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps({
        "workloads": [{"app": "htap_stream", "scale": 0.005,
                       "num_kernels": 2, "windows_per_kernel": 2}],
        "mechanisms": ["nc", "lazypim"], "threads": 8}))
    (tmp_path / "bench" / "traffic" / "toy-grid.json").write_text(json.dumps({
        "loop": "closed", "hw_grid": {"pim_cores": [8, 16, 32]}}))
    (tmp_path / "bench" / "metrics" / "toy.lanes_per_study.py").write_text(
        "def read(run):\n"
        "    return run.studies[0].real_lane_windows / 4 if run.studies "
        "else None\n")
    b["configs"].append({"name": "toy", "source": "a test", "reduced": [],
                         "file": "bench/configs/toy.json", "why": "a test"})
    b["workloads"].append({"name": "toy-cell", "config": "toy",
                           "traffic": "toy-grid", "chips": 1, "why": "a test"})
    b["per_layer"].append({"name": "toy.lanes_per_study", "unit": "lanes",
                           "better": "higher", "source": "program_counter",
                           "layer": "planner", "moves": "sim_windows_per_s",
                           "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    out = harness.run_cell("toy-cell", 5, 0.2, True, t_start=0.0,
                           platform=None, root=tmp_path, cache=False)
    assert out["correct"] is True
    assert out["metrics"]["toy.lanes_per_study"]["value"] == 3.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
