"""The one traffic generator: it reads a mix's data file and yields the
requests of a run.

A mix is a JSON file ``bench/traffic/<mix>.json``.  Today's loop kind:

* ``"loop": "closed"`` — one client submits whole studies back to back,
  the next when the last has returned.  Each study is the configuration's
  workload list over the mix's hardware grid, with every mechanism the
  configuration names.  ``"hw_grid"`` maps a hardware field to either a
  list of values or ``{"start", "step", "num"}``; absent or null means the
  configuration's single hardware point.  Every study gets a trace seed of
  its own, drawn from the run's seed and the study's index, so the same
  run seed gives the same studies and every seed gives studies of the same
  sizes.

A study spec is a plain dict (``workloads``, ``hw``, ``hw_grid``,
``mechanisms``, ``lazy``, ``threads``); the harness turns it into the
program's ``Study`` and the reference reads it as it stands.
"""

from __future__ import annotations

import numpy as np

WARMUP = -1  # study index of the set-up study (never one the window runs)


def study_seed(run_seed: int, index: int) -> int:
    """A trace seed in [0, 2**31) for study ``index`` of a run."""
    ss = np.random.SeedSequence([abs(int(run_seed)), index + 1, 0x5EED])
    return int(ss.generate_state(1)[0]) & 0x7FFFFFFF


def grid_values(axis) -> list[float]:
    if isinstance(axis, dict):
        return [float(axis["start"] + i * axis["step"])
                for i in range(int(axis["num"]))]
    return [float(v) for v in axis]


def study_spec(config: dict, mix: dict, run_seed: int, index: int,
               workload_kw: dict | None = None) -> dict:
    """Study ``index`` of a run of ``mix`` over ``config``.  ``workload_kw``
    overrides trace keywords of every workload (the tests' tiny sizes)."""
    if mix.get("loop") != "closed":
        raise ValueError(f"unknown loop kind {mix.get('loop')!r}")
    seed = study_seed(run_seed, index)
    workloads = []
    for w in config["workloads"]:
        w = {**w, **(workload_kw or {}), "seed": seed}
        workloads.append(w)
    grid = {k: grid_values(v) for k, v in (mix.get("hw_grid") or {}).items()}
    return dict(workloads=workloads, hw=dict(config.get("hw", {})),
                hw_grid=grid, mechanisms=list(config["mechanisms"]),
                lazy=dict(config.get("lazy", {})),
                threads=int(config.get("threads", 16)), seed=seed)


def hw_points(spec: dict) -> list[dict]:
    """The spec's hardware points in the program's grid order (the last
    axis fastest), each as the full set of overridden fields."""
    pts = [dict(spec["hw"])]
    for name, values in spec["hw_grid"].items():
        pts = [{**p, name: v} for p in pts for v in values]
    return pts
