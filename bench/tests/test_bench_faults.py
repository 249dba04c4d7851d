"""The correctness check must fail what it exists to catch, at tiny sizes
on the CPU: the bfloat16 control (the reference one precision below the
configuration's float32, in the program's place), and the timed path
broken underneath a whole harness run."""

import dataclasses
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import control  # noqa: E402
import harness  # noqa: E402

TINY = dict(num_kernels=2, windows_per_kernel=3, scale=0.01)
CELLS = ["large-bwsweep", "htap-fig7"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_reads_above_the_limit_and_the_program_below(cell):
    out = control.readings(cell, [11, 2**31 + 5], platform=None,
                           workload_kw=TINY, cache=False, log=sys.stderr)
    assert out["program_max"] <= harness.MAX_REL_GAP < out["control_min"]


def _state_unchanged(orig):
    def fault(*a, **k):
        return {m: {f: np.zeros_like(v) for f, v in acc.items()}
                for m, acc in orig(*a, **k).items()}
    return fault


def _half_batch(orig):
    def fault(*a, **k):
        out = {}
        for m, acc in orig(*a, **k).items():
            out[m] = {}
            for f, v in acc.items():
                v = np.array(v)
                h = v.shape[0] // 2
                if h:
                    v[h:] = v[:h].mean(axis=0)
                out[m][f] = v
            # lanes past the first half: the mean of the rest, not computed
        return out
    return fault


def _answer_altered(orig):
    def fault(*a, **k):
        r = orig(*a, **k)
        return dataclasses.replace(r, time_ns=r.time_ns * (1 + 1e-3))
    return fault


FAULTS = {
    "state_unchanged": ("repro.sim.engine", "_sweep_accs", _state_unchanged),
    "half_batch": ("repro.sim.engine", "_sweep_accs", _half_batch),
    "answer_altered": ("repro.sim.study", "finalize_result", _answer_altered),
}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_not_correct(monkeypatch, cell, fault):
    import importlib

    modname, attr, make = FAULTS[fault]
    mod = importlib.import_module(modname)
    monkeypatch.setattr(mod, attr, make(getattr(mod, attr)))
    out = harness.run_cell(cell, 2**31 + 17, 0.2, False, t_start=0.0,
                           platform=None, workload_kw=TINY, cache=False)
    assert out["correct"] is False
    assert out["checks"]["max_rel_gap"]["value"] > harness.MAX_REL_GAP

