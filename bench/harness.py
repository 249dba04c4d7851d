"""The benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file (``configs``' ``file``), its traffic mix
(``bench/traffic/<traffic>.json``, read by :mod:`traffic`) and one reader
per per-layer metric (``bench/metrics/<metric>.py``, a ``read(run)``
function that returns a number or ``None``).

A run: JAX must see at least the cell's chips of the stated platform
(``tpu``), or the run exits non-zero and prints no result.  Set-up runs
one study of the cell's traffic (every shape the window uses) and counts as
``setup_s`` from process start.  The window then runs whole studies back to
back for ``--seconds``; with ``--trace 1`` the profiler records the studies
that start in the first ``TRACE_SECONDS`` of it, and the per-layer metrics
are read from that trace and the benchmark's own spans.
After the window one study drawn from the seed is recomputed by the plain
reference (:mod:`reference`) and compared field by field; the numbers
compared are printed with their limits as the last lines of standard
error, and the result is the last line of standard output.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import traffic

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

# The comparison with the reference (PERF.md, "How correct is decided"):
# the widest relative gap over every field of every lane and mechanism of
# the study checked.  Sound float32 runs read at most ~1e-6 (bit-exact on
# the CPU); the bfloat16 control reads 1e-2 and more.
MAX_REL_GAP = 1e-4
# A traced run records the studies that start in its first seconds only:
# the TPU profiler drops device events past a few million per trace (a
# 20 s window of large-bwsweep lost a fifth of its device time).
TRACE_SECONDS = 5.0


class NoDevice(RuntimeError):
    """JAX sees no device of the platform the cell runs on, or too few."""


@dataclasses.dataclass
class StudyRecord:
    index: int
    seed: int
    t1: float = 0.0                 # host clock at the study's end
    prep_s: float = 0.0
    run_s: float = 0.0
    dispatch: list = dataclasses.field(default_factory=list)  # (mech, wall_s)
    real_lane_windows: int = 0      # real windows of real lanes, per mechanism
    mechanisms: int = 0
    padded_line_lanes: int = 0
    real_line_lanes: int = 0
    error: str | None = None

    @property
    def work(self) -> int:
        return self.real_lane_windows * self.mechanisms


@dataclasses.dataclass
class Run:
    """What the per-layer metric readers get."""

    chips: int
    studies: list                   # StudyRecord of each completed study
    trace: object = None            # trace_reduce.Trace or None
    trace_window: tuple = (0, 0)    # window bounds on the trace clock (ns)


class Spans:
    """The benchmark's own spans around its calls into each layer: kept on
    the host clock, and written into the profiler's trace when one is
    recording."""

    def __init__(self, tracing: bool):
        self.tracing = tracing

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.tracing:
            import jax

            with jax.profiler.TraceAnnotation("bench:" + name):
                yield
        else:
            yield


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json "
                     f"(have {[c['name'] for c in bench['workloads']]})")


def load_config(bench: dict, cell: dict, root: pathlib.Path = ROOT) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return json.loads((root / entry["file"]).read_text())


def load_mix(cell: dict, root: pathlib.Path = ROOT) -> dict:
    path = root / HERE.name / "traffic" / f"{cell['traffic']}.json"
    return json.loads(path.read_text())


def metric_reader(name: str, root: pathlib.Path = ROOT):
    path = root / HERE.name / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def make_study(spec: dict):
    """The program's ``Study`` for a traffic spec."""
    from repro.api import Study, grid, workload
    from repro.core.coherence import LazyPIMConfig
    from repro.sim.costmodel import HWParams

    wls = []
    for w in spec["workloads"]:
        kw = {k: v for k, v in w.items() if k not in ("app", "graph")}
        wls.append(workload(w["app"], w.get("graph"), **kw))
    base = HWParams(**spec["hw"])
    hw = grid(base, **spec["hw_grid"]) if spec["hw_grid"] else base
    return Study(workloads=wls, hw=hw, mechanisms=tuple(spec["mechanisms"]),
                 lazy=LazyPIMConfig(**spec["lazy"]), threads=spec["threads"])


def run_study(spec: dict, rec: StudyRecord, span: Spans, devices: int):
    """One request, spec to ``ResultSet``, with the benchmark's spans around
    the host prep, the run and every compiled-scan dispatch."""
    with span(f"study:{rec.index}"):
        study = make_study(spec)
        t = time.perf_counter()
        with span("prep"):
            study.traces()
            study.bucket_lanes()
        rec.prep_s = time.perf_counter() - t

        def on_dispatch(info, thunk):
            t0 = time.perf_counter()
            with span("dispatch:" + info.mechanism):
                out = thunk()
            rec.dispatch.append((info.mechanism, time.perf_counter() - t0))
            return out

        t = time.perf_counter()
        with span("run"):
            rs = study.run(engine="batch", on_dispatch=on_dispatch,
                           devices=devices)
        rec.run_s = time.perf_counter() - t
    rec.t1 = time.perf_counter()
    tts = study.traces()
    rec.real_lane_windows = sum(tts[w].num_windows for w, _, _ in study._lanes())
    rec.mechanisms = len(study.mechanisms)
    plan = study.plan(devices=devices)
    rec.padded_line_lanes = sum(b["num_lines"] * b["lanes"] for b in plan.buckets)
    rec.real_line_lanes = sum(tts[w].num_lines for w, _, _ in study._lanes())
    return rs


def scan_compiles() -> int:
    from repro.sim.engine import sweep_cache_sizes

    return sum(sweep_cache_sizes().values())


class CompileCounter:
    """Counts every backend compile in the process (JAX's own event)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# Correctness: the plain reference
# ---------------------------------------------------------------------------


def reference_results(spec: dict, precision: str = "float32"):
    """{(workload index, hw index): {mechanism: fields}} by the reference."""
    from reference import sim as RM, synth as RS

    out, names = {}, {}
    pts = traffic.hw_points(spec)
    for wi, w in enumerate(spec["workloads"]):
        kw = {k: v for k, v in w.items() if k not in ("app", "graph")}
        tr = RS.make_trace(w["app"], w.get("graph"), threads=spec["threads"],
                           **kw)
        names[wi] = tr["name"]
        prep = RM.Prepared(tr)
        for hi, hw in enumerate(pts):
            out[(wi, hi)] = {m: RM.simulate(prep, hw, m, spec["lazy"], precision)
                             for m in spec["mechanisms"]}
    return out, names


def max_rel_gap(got: dict, want: dict) -> float:
    """Widest |got - want| / max(|want|, 1) over every shared key of every
    point and mechanism; a missing point, mechanism or field reads inf."""
    worst = 0.0
    for key, mechs in want.items():
        for m, fields in mechs.items():
            have = got.get(key, {}).get(m)
            if have is None:
                return float("inf")
            for f, v in fields.items():
                if f not in have:
                    return float("inf")
                gap = abs(have[f] - v) / max(abs(v), 1.0)
                if gap != gap:  # NaN
                    return float("inf")
                worst = max(worst, gap)
    return worst


def program_results(rs, spec: dict) -> tuple[dict, dict]:
    """The program's ResultSet keyed like :func:`reference_results`."""
    H = len(traffic.hw_points(spec))
    out, names = {}, {}
    for j, p in enumerate(rs.points):
        wi, hi = divmod(j, H)
        names[wi] = p.workload
        if p.hw_index != hi:
            continue  # the point lands where no reference key expects it
        out[(wi, hi)] = {m: dataclasses.asdict(r) for m, r in p.results.items()}
    return out, names


def check_study(rs, spec: dict) -> float:
    want, wnames = reference_results(spec)
    got, gnames = program_results(rs, spec)
    if wnames != gnames:
        return float("inf")
    return max_rel_gap(got, want)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def device_info(chips: int, platform: str | None):
    import jax

    devs = jax.devices()
    if platform is not None and devs[0].platform != platform:
        raise NoDevice(f"JAX found {len(devs)} {devs[0].platform} device(s), "
                       f"no {platform}; this benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} devices, JAX sees {len(devs)}")
    return devs


def setup_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, so every run of a cell after its first finds its programs."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, platform: str | None = "tpu",
             root: pathlib.Path = ROOT, workload_kw: dict | None = None,
             cache: bool = True, log=sys.stderr) -> dict:
    """One run of one cell; returns the result line as a dict.  The CPU
    rehearsal tests pass ``platform=None`` (no look for a chip),
    ``workload_kw`` (tiny trace sizes) and ``cache=False``."""
    counter = CompileCounter()
    try:
        return _run_cell(cell_name, seed, seconds, trace, t_start, platform,
                         root, workload_kw, cache, log, counter)
    finally:
        counter.close()


def _run_cell(cell_name, seed, seconds, trace, t_start, platform, root,
              workload_kw, cache, log, counter) -> dict:
    bench = load_benchmark(root)
    cell = find_cell(bench, cell_name)
    config = load_config(bench, cell, root)
    mix = load_mix(cell, root)
    chips = int(cell["chips"])
    devs = device_info(chips, platform)
    if cache:
        setup_cache(root)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    span = Spans(tracing=False)

    warm = traffic.study_spec(config, mix, seed, traffic.WARMUP, workload_kw)
    run_study(warm, StudyRecord(traffic.WARMUP, warm["seed"]), span, chips)
    compiles0, scans0 = counter.n, scan_compiles()

    trace_dir = root / ".bench_trace" / cell_name
    if trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's spans are enough
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = Spans(tracing=True)
    studies, results = [], []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    span_s = min(seconds, TRACE_SECONDS) if trace else seconds
    with span("window"):
        while time.perf_counter() - t0 < span_s:
            i = len(studies)
            spec = traffic.study_spec(config, mix, seed, i, workload_kw)
            rec = StudyRecord(i, spec["seed"])
            try:
                rs = run_study(spec, rec, span, chips)
            except Exception as e:  # a failed request is counted, not fatal
                rec.error = f"{type(e).__name__}: {e}"
                rec.t1 = time.perf_counter()
                rs = None
            studies.append(rec)
            results.append((spec, rs))
    t_end = max(r.t1 for r in studies)
    window_s = t_end - t0
    compiles, scans = counter.n - compiles0, scan_compiles() - scans0
    if trace:
        jax.profiler.stop_trace()
    mem = peak_bytes(devs[:chips])
    print(f"window: {len(studies)} studies in {window_s:.6f} s; compiles "
          f"inside the window {compiles} (scan compiles {scans})", file=log)

    ok = [r for r in studies if r.error is None]
    failed = len(studies) - len(ok)
    for r in studies:
        if r.error:
            print(f"study {r.index} failed: {r.error}", file=log)
    metrics = {}
    run = Run(chips=chips, studies=ok)
    breakdown = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace:
        import trace_reduce as TR

        path = TR.newest_xplane(str(trace_dir))
        tr = TR.load(path) if path else TR.Trace(ops={}, spans=[])
        shutil.rmtree(trace_dir, ignore_errors=True)  # hundreds of MB
        offsets = TR.align(tr)
        print(f"trace: device clock offsets {offsets} ns", file=log)
        win = TR.spans_named(tr, "bench:window")
        lo, hi = (win[0][0], win[0][1]) if win else (0, 0)
        run.trace, run.trace_window = tr, (lo, hi)
        busy = TR.busy_per_device(tr, lo, hi)
        used = sorted(busy)[:chips]
        device["busy_s"] = (sum(busy[d] for d in used) / len(used) / 1e9
                            if used else 0.0)
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": TR.top_ops(tr, lo, hi),
                     "idle_gaps": TR.idle_gaps(tr, lo, hi)}
        for m in cell_metrics(bench, cell, "per_layer"):
            v = metric_reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "sim_windows_per_s": (sum(r.work for r in ok) / window_s
                                     if ok and window_s > 0 else None)}
        for m in cell_metrics(bench, cell, "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # -- correctness, after the window, memory read and the program's own
    #    state let go: one completed study drawn from the seed --------------
    import numpy as np

    done = [(s, rs) for (s, rs), r in zip(results, studies) if r.error is None]
    del results
    if done:
        pick = int(np.random.default_rng(abs(int(seed))).integers(len(done)))
        spec, rs = done[pick]
        t = time.perf_counter()
        gap = check_study(rs, spec)
        print(f"check: study {pick} of {len(done)} (seed {spec['seed']}), "
              f"{len(rs)} lanes x {len(spec['mechanisms'])} mechanisms "
              f"against the reference in {time.perf_counter() - t:.3f} s",
              file=log)
    else:
        gap = float("inf")
    checks = {"max_rel_gap": {"value": gap, "limit": MAX_REL_GAP},
              "failed_studies": {"value": failed, "limit": 0}}
    correct = gap <= MAX_REL_GAP and failed == 0
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=log)
    out = {"correct": bool(correct), "attempted": len(studies),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0
