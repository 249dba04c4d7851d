"""Captured-workload mechanism study: live model streams vs their
synthetic analogues.

One declarative ``Study`` over the three captured families
(:mod:`repro.capture`) and the synthetic family each one is the live
analogue of:

    capture/kv_serve     ~  htap_stream     (hot-tail append + lagged reads)
    capture/moe_experts  ~  mtmix-enron     (two tenants over shared data)
    capture/lazy_embed   ~  pagerank-enron  (scattered row update/read races)

For every workload the paper's mechanism ordering is checked —
``ideal >= lazypim`` and ``lazypim >= fg``/``cg`` on speedup over CPU —
and the committed ``BENCH_capture.json`` records per-workload speedups,
the ordering flags (where the paper's story *holds or inverts* on real
streams), the arithmetic-intensity profiles
(:func:`repro.roofline.analysis.trace_intensity`), and the study's
``plan()``-predicted vs measured compile counts, which
``benchmarks/check_budget.py`` gates in CI.

``--smoke`` runs the CI-sized leg: a tiny capture (2 decode steps), a
validity + determinism assert, and one Study point through the batched
planner with plan == measured compiles — no JSON is written.
"""

from __future__ import annotations

import json
import pathlib

from repro.api import Study
from repro.sim.engine import sweep_cache_sizes

ANALOGUE_OF = {
    "capture/kv_serve": "htap_stream",
    "capture/moe_experts": "mtmix-enron",
    "capture/lazy_embed": "pagerank-enron",
}

# Speedup-over-CPU orderings the paper's synthetic evaluation establishes
# (§7): checked per workload, recorded as hold/invert flags.
ORDERINGS = (("ideal", "lazypim"), ("lazypim", "fg"), ("lazypim", "cg"))


def study(threads: int = 16) -> Study:
    """THE capture study: 3 captured workloads + 3 synthetic analogues ×
    every mechanism (also the live compile fixture for check_budget)."""
    workloads = list(ANALOGUE_OF) + sorted(set(ANALOGUE_OF.values()))
    return Study(workloads=workloads, threads=threads)


def run(threads: int = 16) -> dict:
    st = study(threads)
    plan = st.plan()
    predicted = plan.compiles_per_mechanism
    before = sweep_cache_sizes(st.mechanisms)
    rs = st.run()
    after = sweep_cache_sizes(st.mechanisms)
    measured = {m: after[m] - before[m] for m in st.mechanisms}

    rows = {p.workload: n for p, n in zip(rs.points, rs.normalized())}
    ordering = {}
    for name, r in rows.items():
        flags = {}
        for hi, lo in ORDERINGS:
            flags[f"{hi}>={lo}"] = bool(r[hi]["speedup"] >= r[lo]["speedup"])
        ordering[name] = flags

    from repro.roofline.analysis import trace_intensity
    from repro.sim.trace import make_trace

    intensity = {}
    for app in ANALOGUE_OF:
        intensity[app] = trace_intensity(make_trace(app, threads=threads))

    return {
        "workloads": {name: {m: {"speedup": round(r[m]["speedup"], 6),
                                 "traffic": round(r[m]["traffic"], 6)}
                             for m in r}
                      for name, r in rows.items()},
        "ordering": ordering,
        "analogue_of": ANALOGUE_OF,
        "intensity": intensity,
        "plan_compiles_per_mechanism": predicted,
        "measured_compiles_per_mechanism": measured,
        "plan_matches_measured": measured == predicted,
        "total_compiles": sum(measured.values()),
    }


def smoke() -> None:
    """CI capture smoke: tiny config, 2 decode steps, one Study point
    through the batched planner, plan == measured."""
    import numpy as np

    from repro.sim.prep import bucket_bound, prepare
    from repro.sim.trace import make_trace

    kw = dict(num_kernels=2, windows_per_kernel=2, scale=0.05, seed=0)
    tr = make_trace("capture/kv_serve", **kw)
    assert tr.num_windows >= 2 and tr.num_kernels == 2
    assert tr.num_lines == bucket_bound(tr.num_lines)
    prepare(tr)
    again = make_trace("capture/kv_serve", **kw)
    assert np.array_equal(tr.pim_writes, again.pim_writes), "nondeterministic"

    # route the tiny geometry through the planner by handing it the
    # prepared trace directly (Study accepts TraceTensors)
    st = Study(workloads=[prepare(tr)], threads=16)
    plan = st.plan().compiles_per_mechanism
    before = sweep_cache_sizes(st.mechanisms)
    rs = st.run()
    after = sweep_cache_sizes(st.mechanisms)
    measured = {m: after[m] - before[m] for m in st.mechanisms}
    assert measured == plan, f"plan {plan} != measured {measured}"
    [point] = rs.normalized()
    assert point["lazypim"]["speedup"] > 0
    print(f"fig_capture --smoke: W={tr.num_windows} lines={tr.num_lines} "
          f"compiles={sum(measured.values())} (plan exact), "
          f"lazypim speedup {point['lazypim']['speedup']:.3f}")


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None,
                    help="output path (default: repo-root BENCH_capture.json)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    record = run()
    out = pathlib.Path(args.out) if args.out else \
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_capture.json"
    out.write_text(json.dumps({"capture": record}, indent=1,
                              sort_keys=True) + "\n")
    for name, flags in record["ordering"].items():
        tag = "" if name.startswith("capture/") else "  (synthetic)"
        holds = ", ".join(f"{k}={'holds' if v else 'INVERTS'}"
                          for k, v in flags.items())
        print(f"{name:22s} {holds}{tag}")
    print(f"fig_capture: plan_matches_measured="
          f"{record['plan_matches_measured']}, "
          f"{record['total_compiles']} compiles -> {out}")


if __name__ == "__main__":
    main()
