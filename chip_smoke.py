"""Bring-up smoke: the simulator's main path and its served path on a TPU.

    python chip_smoke.py              # phases 1-5 on one chip
    python chip_smoke.py --chips 4    # phase 4 only, devices=4 vs devices=1

Phases, in order, each printing one JSON line of its own results:

1. ``device``  — JAX must see a TPU; on any other platform the script
   exits non-zero and never carries on.
2. ``golden``  — the two golden workloads (pagerank-arxiv, htap128; 16
   threads, default ``HWParams``, all six mechanisms) through
   ``Study.run(engine="batch")``, held to ``tests/golden/
   fig7_batched_golden.json`` at the golden tests' own tolerances.
3. ``fleet``   — the fig7 study (22 workloads x 6 mechanisms); the
   planner's compile prediction must equal the measured jit-cache deltas.
4. ``large``   — htap128-large (1,010,524 lines, the 1 Mi-line bucket) over
   an 8-point ``offchip_bw_gbs`` grid at ``devices=1``; lane 0 must be
   bit-exact with ``engine="sequential"`` on every ``SimResult`` field.
5. ``served``  — an in-process ``StudyServer`` answers a few requests,
   one of them a repeat: every answer ``ok`` from ``engine="batch"``, no
   engine failure, no degraded dispatch, and no scan compile for the
   repeat.

With ``--chips 4`` only phase 4 runs, at ``devices=4`` and at
``devices=1``: bit-exact on every field, with the device of every output
shard printed.  Any exception or mismatch exits non-zero.  The last line
of standard output is ``{"ok": true, "device": {...}}`` and nothing else.

The script reads only tracked files and what it generates from seeds.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

GOLDEN_PATH = ROOT / "tests" / "golden" / "fig7_batched_golden.json"
GOLDEN_WORKLOADS = ("pagerank-arxiv", "htap128")
RATIO_KEYS = ("speedup", "traffic", "energy")
RATIO_RTOL = 1e-6  # tests/test_golden_figures.py
RAW_RTOL = 1e-4

_SMALL = dict(num_kernels=3, windows_per_kernel=2)
SERVED_SPECS = (
    {"workloads": [{"app": "pagerank", "graph": "arxiv", "scale": 0.4,
                    **_SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
    {"workloads": [{"app": "htap128", "scale": 0.004, **_SMALL}],
     "hw_grid": {"offchip_bw_gbs": [16.0, 32.0]}},
)


class SmokeFailure(RuntimeError):
    """A phase's result is wrong (as opposed to crashing on the way)."""


def _emit(phase: str, **fields) -> dict:
    rec = {"phase": phase, **fields}
    print(json.dumps(rec, default=str), flush=True)
    return rec


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _compile_delta(before: dict, after: dict) -> dict:
    return {m: after[m] - before[m] for m in after}


def phase_device(platform: str = "tpu", min_count: int = 1) -> dict:
    """JAX must see ``min_count`` devices of ``platform``."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != platform:
        raise SmokeFailure(
            f"no TPU: JAX found {len(devs)} {d0.platform} device(s) "
            f"({d0.device_kind}); this smoke runs only on the chip")
    if len(devs) < min_count:
        raise SmokeFailure(f"need {min_count} {platform} devices, JAX sees "
                           f"{len(devs)}")
    return _emit("device", platform=d0.platform, kind=d0.device_kind,
                 count=len(devs))


def golden_mismatches(summaries: dict, golden: dict) -> list[str]:
    """Fields of ``summaries`` (workload -> mechanism -> summarize() dict)
    outside the golden tests' tolerances, by name."""
    bad = []
    if set(summaries) != set(golden):
        return [f"workloads {sorted(summaries)} != golden {sorted(golden)}"]
    for name, mechs in golden.items():
        if set(summaries[name]) != set(mechs):
            bad.append(f"{name}: mechanisms {sorted(summaries[name])} != "
                       f"golden {sorted(mechs)}")
            continue
        for mech, vals in mechs.items():
            for key, want in vals.items():
                got = summaries[name][mech][key]
                tol = RATIO_RTOL if key in RATIO_KEYS else RAW_RTOL
                if abs(got - want) / max(abs(want), 1e-12) >= tol:
                    bad.append(f"{name}/{mech}/{key}: {got!r} != golden "
                               f"{want!r}")
    return bad


def phase_golden(workloads=GOLDEN_WORKLOADS, golden_path=GOLDEN_PATH,
                 **study_kw) -> dict:
    from repro.api import Study

    golden = json.loads(pathlib.Path(golden_path).read_text())
    study = Study(workloads=list(workloads), threads=16, **study_kw)
    rs, wall = _timed(lambda: study.run(engine="batch"))
    summaries = {p.workload: s for p, s in zip(rs.points, rs.normalized())}
    bad = golden_mismatches(summaries, golden)
    if bad:
        raise SmokeFailure("golden mismatch: " + "; ".join(bad))
    return _emit("golden", workloads=list(summaries), wall_s=wall,
                 fields_checked=sum(len(v) for m in golden.values()
                                    for v in m.values()),
                 peak_bytes=_peak_bytes())


def phase_fleet(study) -> dict:
    from repro.sim.engine import sweep_cache_sizes

    _, synth_s = _timed(study.traces)
    plan = study.plan()
    before = sweep_cache_sizes(study.mechanisms)
    rs, cold_s = _timed(study.run)
    measured = _compile_delta(before, sweep_cache_sizes(study.mechanisms))
    if measured != plan.compiles_per_mechanism:
        raise SmokeFailure(f"fleet: planned compiles "
                           f"{plan.compiles_per_mechanism} != measured "
                           f"{measured}")
    rs2, warm_s = _timed(study.run)
    if rs.to_rows() != rs2.to_rows():
        raise SmokeFailure("fleet: warm rerun differs from the cold run")
    return _emit("fleet", points=len(rs), mechanisms=len(study.mechanisms),
                 buckets=plan.num_buckets, compiles=measured,
                 synth_s=synth_s, cold_s=cold_s, warm_s=warm_s,
                 peak_bytes=_peak_bytes())


def _diff_fields(a, b) -> list[str]:
    """Names of the SimResult fields where two ResultSets differ."""
    bad = []
    for pa, pb in zip(a.points, b.points):
        for m, ra in pa.results.items():
            da, db = dataclasses.asdict(ra), dataclasses.asdict(pb.results[m])
            bad += [f"{pa.workload}[hw{pa.hw_index}]/{m}/{k}: {da[k]!r} != "
                    f"{db[k]!r}" for k in da if da[k] != db[k]]
    if len(a.points) != len(b.points):
        bad.append(f"{len(a.points)} points != {len(b.points)}")
    return bad


def phase_large(study, devices: int = 1) -> dict:
    from repro.api import Study

    _, synth_s = _timed(study.traces)
    rs, cold_s = _timed(lambda: study.run(devices=devices))
    _, warm_s = _timed(lambda: study.run(devices=devices))
    lane0 = Study(workloads=study.traces()[:1], hw=study.hw_points()[0],
                  mechanisms=study.mechanisms, lazy=study.lazy_points()[0])
    ref, seq_s = _timed(lambda: lane0.run(engine="sequential"))
    got = type(rs)(rs.points[:1], rs.mechanisms)
    bad = _diff_fields(got, ref)
    if bad:
        raise SmokeFailure("large: lane 0 != sequential: " + "; ".join(bad))
    return _emit("large", lines=study.traces()[0].num_lines,
                 lanes=study.num_points, devices=devices, synth_s=synth_s,
                 cold_s=cold_s, warm_s=warm_s, sequential_s=seq_s,
                 peak_bytes=_peak_bytes())


def phase_served(specs=SERVED_SPECS) -> dict:
    from repro.serve import OK, ServeConfig, StudyServer, build_study
    from repro.sim.engine import sweep_cache_sizes

    server = StudyServer(ServeConfig(default_deadline_s=900.0))
    t0 = time.perf_counter()
    for spec in specs:
        server.submit(spec)
    out = server.drain()
    first_s = time.perf_counter() - t0
    before = sweep_cache_sizes()
    server.submit(specs[0])
    repeat, repeat_s = _timed(server.drain)
    repeat_compiles = sum(_compile_delta(before, sweep_cache_sizes()).values())
    out += repeat
    bad = [f"rid {r.rid}: {r.status} engine={r.engine} ({r.error})"
           for r in out if r.status != OK or r.engine != "batch"]
    for key in ("engine_failures", "degraded_dispatches"):
        if server.stats[key]:
            bad.append(f"stats[{key!r}] = {server.stats[key]}")
    if len(out) != len(specs) + 1:
        bad.append(f"{len(out)} responses for {len(specs) + 1} requests")
    if repeat_compiles:
        bad.append(f"the repeat request compiled {repeat_compiles} scans")
    if not bad:
        out.sort(key=lambda r: r.rid)
        ref = build_study(specs[0]).run(engine="sequential")
        bad += _diff_fields(out[0].results, ref)
        bad += _diff_fields(out[-1].results, out[0].results)
    if bad:
        raise SmokeFailure("served: " + "; ".join(bad))
    return _emit("served", requests=len(out), first_s=first_s,
                 repeat_s=repeat_s, repeat_compiles=repeat_compiles,
                 stats=dict(server.stats), peak_bytes=_peak_bytes())


def shard_placement(study, devices: int) -> dict:
    """Where the output shards of one sharded dispatch live: the stacked
    accumulator of the ``cpu`` scan over the study's single bucket."""
    from repro.sim import engine

    (bl,) = study.bucket_lanes()
    stt = engine.neutral_trace(engine.stack_traces(bl.traces))
    acc = engine._sweep_fn_mesh("cpu", devices)(stt, engine.stack_hw(bl.hws))
    leaf = next(iter(acc.values()))
    return {"sharding": str(leaf.sharding),
            "shards": [{"device": str(s.device), "lanes": s.data.shape[0]}
                       for s in leaf.addressable_shards]}


def phase_mesh(study, devices: int) -> dict:
    """Phase 4 at ``devices`` and at 1: bit-exact on every field, and the
    output shards spread over ``devices`` distinct devices."""
    _, synth_s = _timed(study.traces)
    walls = {}
    results = {}
    for d in (devices, 1):
        results[d], cold_s = _timed(lambda d=d: study.run(devices=d))
        _, warm_s = _timed(lambda d=d: study.run(devices=d))
        walls[d] = {"cold_s": cold_s, "warm_s": warm_s}
    bad = _diff_fields(results[devices], results[1])
    if bad:
        raise SmokeFailure(f"mesh: devices={devices} != devices=1: "
                           + "; ".join(bad))
    placement = shard_placement(study, devices)
    used = {s["device"] for s in placement["shards"]}
    if len(used) != devices:
        raise SmokeFailure(f"mesh: output shards on {sorted(used)}, want "
                           f"{devices} distinct devices")
    return _emit("mesh", lines=study.traces()[0].num_lines,
                 lanes=study.num_points, synth_s=synth_s,
                 walls={str(d): w for d, w in walls.items()},
                 placement=placement, peak_bytes=_peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the large phase, sharded over four "
                         "chips and compared with one")
    args = ap.parse_args(argv)
    # The repo's modules first: a copy of this script without the repo
    # around it fails here, before it prints anything.
    from benchmarks.bench_engine import large_study
    from benchmarks.fig7_speedup import study as fig7_study
    from repro.runtime.compile_cache import setup_compile_cache

    try:
        dev = phase_device(min_count=args.chips)
        setup_compile_cache()
        if args.chips == 4:
            phase_mesh(large_study(), devices=4)
        else:
            phase_golden()
            phase_fleet(fig7_study())
            phase_large(large_study(), devices=1)
            phase_served()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
