"""Golden-figure regression tests.

``tests/golden/fig7_golden.json`` pins the full ``summarize()`` output
(fig7-style speedup / normalized-traffic / energy plus the raw
accumulators) for two small workloads under the default ``HWParams``.
Any drift in trace synthesis, the packed engine, the cost model or the
signature configuration shows up here as a tier-1 failure instead of a
silently shifted benchmark table.

``tests/golden/fig7_batched_golden.json`` pins the SAME workloads through
the geometry-bucketed batch engine (the ``Study`` planner): the
numbers must match the sequential-path golden to 1e-6 — same results,
different engine — so a padding/bucketing regression surfaces here even if
both goldens were regenerated together.

Ratios (speedup / traffic / energy) are asserted to 1e-6 relative; the raw
accumulator magnitudes to 1e-4 (they are float32 sums — the ratios are the
paper's reported quantities and the tighter contract).

Regenerate (only after an *intentional* model change) with:

    PYTHONPATH=src python -m tests.test_golden_figures
"""

from __future__ import annotations

import json
import pathlib

import pytest

from repro.sim.costmodel import HWParams
from repro.sim.engine import run_all, summarize
from repro.sim.prep import prepare
from repro.sim.study import Study
from repro.sim.trace import make_trace

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
GOLDEN_PATH = GOLDEN_DIR / "fig7_golden.json"
BATCHED_GOLDEN_PATH = GOLDEN_DIR / "fig7_batched_golden.json"
GOLDEN_WORKLOADS = (("pagerank", "arxiv"), ("htap128", None))
RATIO_KEYS = ("speedup", "traffic", "energy")
RATIO_RTOL = 1e-6
RAW_RTOL = 1e-4


def _current(engine: str = "sequential") -> dict:
    hw = HWParams()
    tts = [prepare(make_trace(app, g, threads=16))
           for app, g in GOLDEN_WORKLOADS]
    if engine == "batch":
        results = [p.results for p in Study(workloads=tts, hw=hw).run().points]
    else:
        results = [run_all(tt, hw) for tt in tts]
    return {tt.name: summarize(r, hw) for tt, r in zip(tts, results)}


@pytest.fixture(scope="module", params=["sequential", "batch"])
def current(request):
    return _current(request.param)


@pytest.fixture(scope="module")
def golden():
    return json.loads(GOLDEN_PATH.read_text())


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def test_golden_workloads_and_mechanisms_present(current, golden):
    assert set(current) == set(golden)
    for name in golden:
        assert set(current[name]) == set(golden[name]), name


def test_fig7_ratios_match_golden(current, golden):
    for name, mechs in golden.items():
        for mech, vals in mechs.items():
            for key in RATIO_KEYS:
                got, want = current[name][mech][key], vals[key]
                assert _rel(got, want) < RATIO_RTOL, \
                    f"{name}/{mech}/{key}: {got!r} != golden {want!r}"


def test_raw_accumulators_match_golden(current, golden):
    for name, mechs in golden.items():
        for mech, vals in mechs.items():
            for key, want in vals.items():
                if key in RATIO_KEYS:
                    continue
                got = current[name][mech][key]
                assert _rel(got, want) < RAW_RTOL, \
                    f"{name}/{mech}/{key}: {got!r} != golden {want!r}"


def test_batched_golden_pins_sequential_golden():
    """The batched-fig7 golden must carry the same numbers as the
    sequential-path golden (1e-6 on ratios, 1e-4 on raw accumulators) —
    the two engines are bit-exact, so the committed artifacts must agree
    too."""
    seq = json.loads(GOLDEN_PATH.read_text())
    bat = json.loads(BATCHED_GOLDEN_PATH.read_text())
    assert set(seq) == set(bat)
    for name in seq:
        assert set(seq[name]) == set(bat[name]), name
        for mech, vals in seq[name].items():
            for key, want in vals.items():
                tol = RATIO_RTOL if key in RATIO_KEYS else RAW_RTOL
                got = bat[name][mech][key]
                assert _rel(got, want) < tol, \
                    f"{name}/{mech}/{key}: batched golden {got!r} != " \
                    f"sequential golden {want!r}"


def main():
    GOLDEN_PATH.write_text(
        json.dumps(_current("sequential"), indent=2, sort_keys=True))
    print(f"wrote {GOLDEN_PATH}")
    BATCHED_GOLDEN_PATH.write_text(
        json.dumps(_current("batch"), indent=2, sort_keys=True))
    print(f"wrote {BATCHED_GOLDEN_PATH}")


if __name__ == "__main__":
    main()
