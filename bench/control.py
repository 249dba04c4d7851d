"""The readings the correctness limit is set from (PERF.md, "How correct is
decided"), for one cell at its own size, in one process:

    python3 bench/control.py --workload <cell> --seeds <n> --first-seed <s>

For each seed it runs study 0 of that seed's traffic through the timed
path (after one set-up study), then reads two numbers against the float32
reference on every lane and mechanism of that study:

* ``program``: the program's widest relative gap (the lower reading);
* ``control``: the same reference computed in bfloat16, the precision
  below the configuration's float32, put in the program's place (the
  upper reading).

The benchmark's own runs never run this.  Prints one JSON line per seed
and a summary line last.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402
import traffic  # noqa: E402


def readings(cell_name: str, seeds: list[int], *, platform: str | None = "tpu",
             workload_kw: dict | None = None, root=harness.ROOT,
             cache: bool = True, log=sys.stdout) -> dict:
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, cell_name)
    config = harness.load_config(bench, cell, root)
    mix = harness.load_mix(cell, root)
    chips = int(cell["chips"])
    harness.device_info(chips, platform)
    if cache:
        harness.setup_cache(root)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    span = harness.Spans(tracing=False)
    warm = traffic.study_spec(config, mix, seeds[0], traffic.WARMUP, workload_kw)
    harness.run_study(warm, harness.StudyRecord(-1, warm["seed"]), span, chips)
    prog, ctrl = [], []
    for s in seeds:
        spec = traffic.study_spec(config, mix, s, 0, workload_kw)
        rs = harness.run_study(spec, harness.StudyRecord(0, spec["seed"]),
                               span, chips)
        t = time.perf_counter()
        want, wnames = harness.reference_results(spec, "float32")
        got, gnames = harness.program_results(rs, spec)
        p = harness.max_rel_gap(got, want) if wnames == gnames else float("inf")
        low, _ = harness.reference_results(spec, "bfloat16")
        c = harness.max_rel_gap(low, want)
        prog.append(p)
        ctrl.append(c)
        print(json.dumps({"seed": s, "program": p, "control": c,
                          "reference_s": time.perf_counter() - t}),
              file=log, flush=True)
    out = {"cell": cell_name, "seeds": len(seeds), "program_max": max(prog),
           "control_min": min(ctrl), "limit": harness.MAX_REL_GAP}
    print(json.dumps(out), file=log, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3000000001)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7 * i for i in range(args.seeds)]
    try:
        readings(args.workload, seeds)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
