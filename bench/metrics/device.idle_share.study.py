"""Device: the share of the traced window in which no operation ran,
averaged over the chips the cell uses, in percent."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as TR  # noqa: E402


def read(run):
    tr = run.trace
    lo, hi = run.trace_window
    if tr is None or not tr.ops or hi <= lo:
        return None
    busy = TR.busy_per_device(tr, lo, hi)
    devs = sorted(busy)[:run.chips]
    return 100.0 * (1.0 - sum(busy[d] for d in devs) / len(devs) / (hi - lo))
