"""The program's ``repro:`` spans in a trace recorded on the CPU profiler and
in the program's own record of them, the readers that reduce that record
(self time and byte counters per study), a traced rehearsal that reports
every reader, and the results of both cells, bit-identical with the
profiler on and off."""

import dataclasses
import pathlib
import sys

import jax
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402
import trace_reduce as TR  # noqa: E402
import traffic  # noqa: E402
from repro.runtime import spans as SP  # noqa: E402

TINY = dict(num_kernels=2, windows_per_kernel=2, scale=0.01)
PROGRAM_METRICS = ["host.synth_s", "host.prepare_s", "host.pack_s",
                   "host.pad_s", "host.stack_s", "host.finalize_s",
                   "host.d2h_mb", "host.h2d_mb"]


def _run(studies=((10.0, 4.0, 6.0), (20.0, 5.0, 5.0))):
    """A run whose studies are (end, prep_s, run_s) on the host clock."""
    recs = [harness.StudyRecord(i, i, t1=t1, prep_s=prep, run_s=run)
            for i, (t1, prep, run) in enumerate(studies)]
    return harness.Run(chips=1, studies=recs)


def _read(name, run):
    return harness.metric_reader(name, ROOT)(run)


def test_self_time_leaves_out_nested_spans_per_study(monkeypatch):
    b = {"d2h_bytes": 0, "h2d_bytes": 0}
    monkeypatch.setattr(SP, "recorded", lambda: [
        (-5.0, 1.0, "repro:prepare", b),     # an earlier traced window
        (0.5, 1.5, "repro:synth", b),
        (1.5, 9.0, "repro:prepare", {"d2h_bytes": 3_000_000, "h2d_bytes": 0}),
        (2.0, 6.0, "repro:pack", b),
        (0.0, 10.0, "repro:traces", b),
        (11.0, 17.0, "repro:prepare", {"d2h_bytes": 1_000_000,
                                       "h2d_bytes": 5_000_000}),
        (12.0, 13.0, "repro:pack", b),
        (12.5, 13.5, "repro:pack", b),       # overlaps its sibling
        (25.0, 30.0, "repro:prepare", b),    # starts after the window
    ])
    # prepare: (7.5 - 4) + (6 - 1.5), over 2 studies
    assert _read("host.prepare_s", _run()) == pytest.approx(8.0 / 2)
    assert _read("host.pack_s", _run()) == pytest.approx((4 + 2) / 2)
    assert _read("host.synth_s", _run()) == pytest.approx(1.0 / 2)
    assert _read("host.d2h_mb", _run()) == pytest.approx(2.0)
    assert _read("host.h2d_mb", _run()) == pytest.approx(2.5)
    assert _read("host.stack_s", _run()) is None


def test_the_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(SP, "recorded", lambda: [])
    assert all(_read(m, _run()) is None for m in PROGRAM_METRICS)
    monkeypatch.setattr(SP, "recorded", lambda: [
        (1.0, 2.0, "repro:pack", {"d2h_bytes": 1, "h2d_bytes": 1})])
    assert all(_read(m, _run(studies=())) is None for m in PROGRAM_METRICS)
    monkeypatch.delitem(sys.modules, "repro.runtime.spans")  # the parent's
    assert all(_read(m, _run()) is None for m in PROGRAM_METRICS)


def _program_events(path):
    """The trace's ``repro:`` host events as (start, end, name, stats), in
    the order they start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SP.PREFIX):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name,
                                dict(e.stats)))
    return sorted(out, key=lambda p: p[:3])


def _leaves_nbytes(tree, distinct=False):
    leaves = jax.tree_util.tree_leaves(tree)
    if distinct:
        leaves = list({id(x): x for x in leaves}.values())
    return sum(x.nbytes for x in leaves)


@pytest.fixture(scope="module")
def traced_study(tmp_path_factory):
    from repro.api import Study, grid, workload
    from repro.sim import engine

    mk = lambda: Study(workloads=[workload("htap128", **TINY)],  # noqa: E731
                       hw=grid(offchip_bw_gbs=[16.0, 32.0]),
                       mechanisms=("cpu", "lazypim"))
    mk().run(devices=1)  # compiles outside the trace
    st = mk()
    d = tmp_path_factory.mktemp("trace")
    before = len(SP.recorded())
    jax.profiler.start_trace(str(d))
    try:
        st.run(devices=1)
    finally:
        jax.profiler.stop_trace()
    tr = _program_events(TR.newest_xplane(str(d)))
    kept = SP.recorded()[before:]
    (bl,) = st.bucket_lanes()
    stacked, shw, scfg = st._stack_lanes(bl, 2, 1)
    accs = engine._sweep_accs(stacked, shw, st.mechanisms, scfg)
    return st, tr, kept, bl, (stacked, shw, scfg), accs


def test_the_trace_holds_every_program_span_with_its_study(traced_study):
    st, tr, _, _, _, _ = traced_study
    names = [p[2] for p in tr]
    for name in ("traces", "synth", "prepare", "pack", "bucket_lanes", "pad",
                 "run", "stack", "scan:cpu", "scan:lazypim", "finalize"):
        assert names.count("repro:" + name) == 1, name
    tops = [p for p in tr if "study" in p[3]]
    assert [p[2] for p in tops] == ["repro:traces", "repro:bucket_lanes",
                                    "repro:run"]
    assert all(p[3]["study"] == st.trace_id for p in tops)
    for p in tr:  # every other span lies inside one of them
        assert any(t[0] <= p[0] and p[1] <= t[1] for t in tops), p[2]
    for p in tr:
        if p[2].startswith("repro:scan:"):
            assert p[3]["lanes"] == 2


def test_the_program_keeps_the_spans_it_wrote_into_the_trace(traced_study):
    _, tr, kept, _, _, _ = traced_study
    kept = sorted(kept, key=lambda p: p[:3])
    assert [(p[2], p[3]) for p in kept] == [(p[2], p[3]) for p in tr]
    for (s, e, _, _), (ts, te, _, _) in zip(kept, tr):
        assert s <= e and ts <= te
    # the same nesting on both clocks
    inside = lambda ps: [[i for i, q in enumerate(ps) if i != j  # noqa: E731
                          and p[0] <= q[0] and q[1] <= p[1]]
                         for j, p in enumerate(ps)]
    assert inside(kept) == inside(tr)


def test_the_byte_counters_equal_the_tiny_shapes(traced_study):
    st, tr, _, bl, (stacked, shw, scfg), accs = traced_study
    by = {p[2]: p[3] for p in tr}
    tt = st.traces()[0]
    read = [tt.pim_reads, tt.pim_writes, tt.cpu_reads, tt.cpu_writes,
            tt.pre_writes]
    put = read[:4] + [tt.pim_r_valid, tt.pim_w_valid, tt.cpu_r_valid,
                      tt.cpu_w_valid, tt.pre_writes, tt.pre_writes_words,
                      tt.pim_uniq_r, tt.pim_uniq_w, tt.pim_uniq,
                      tt.cpu_priv_miss_rate, tt.cpu_reuse]
    assert by["repro:prepare"]["d2h_bytes"] == sum(x.nbytes for x in read)
    assert by["repro:prepare"]["h2d_bytes"] == sum(x.nbytes for x in put)
    # the two lanes share one padded trace: read once, put twice
    assert by["repro:stack"]["d2h_bytes"] == _leaves_nbytes(bl.traces[0],
                                                            distinct=True)
    assert by["repro:stack"]["h2d_bytes"] == (
        _leaves_nbytes(stacked) + _leaves_nbytes(shw) + _leaves_nbytes(scfg))
    assert by["repro:stack"]["h2d_bytes"] == (
        2 * _leaves_nbytes(bl.traces[0]) + _leaves_nbytes(shw)
        + _leaves_nbytes(scfg))
    for m, acc in accs.items():
        assert by["repro:scan:" + m]["d2h_bytes"] == sum(
            np.asarray(v).nbytes for v in acc.values())
        assert by["repro:scan:" + m]["h2d_bytes"] == 0
    for name in ("traces", "synth", "pack", "bucket_lanes", "pad", "run",
                 "finalize"):
        assert by["repro:" + name]["d2h_bytes"] == 0, name
        assert by["repro:" + name]["h2d_bytes"] == 0, name


def test_a_traced_rehearsal_reports_every_program_metric():
    out = harness.run_cell("large-bwsweep", 2**31 + 99, 0.3, True,
                           t_start=0.0, platform=None, workload_kw=TINY,
                           cache=False)
    assert out["correct"] is True
    got = out["metrics"]
    for name in PROGRAM_METRICS:
        assert got[name]["value"] > 0, name
    # the four prep spans lie inside the benchmark's prep clock
    prep = sum(got[m]["value"] for m in PROGRAM_METRICS[:4])
    assert prep < got["host.prep_s"]["value"]


@pytest.mark.parametrize("cell", ["large-bwsweep", "htap-fig7"])
def test_results_are_bit_identical_with_the_profiler_recording(cell,
                                                               tmp_path):
    bench = harness.load_benchmark(ROOT)
    c = harness.find_cell(bench, cell)
    spec = traffic.study_spec(harness.load_config(bench, c),
                              harness.load_mix(c), 2**31 + 17, 0, TINY)

    def fields(rs):
        return [(p.workload, p.hw_index, m, dataclasses.asdict(r))
                for p in rs.points for m, r in p.results.items()]

    off = fields(harness.make_study(spec).run(devices=1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = fields(harness.make_study(spec).run(devices=1))
    finally:
        jax.profiler.stop_trace()
    assert on == off
