"""Mesh: the busiest chip's device busy time over the mean busy time of the
chips the cell uses, in the traced window (1.0: every chip equally busy).
Reads nothing on one chip or where the trace holds no device plane."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as TR  # noqa: E402


def read(run):
    tr = run.trace
    lo, hi = run.trace_window
    if tr is None or run.chips < 2 or len(tr.ops) < run.chips or hi <= lo:
        return None
    busy = TR.busy_per_device(tr, lo, hi)
    used = [busy[d] for d in sorted(busy)[:run.chips]]
    mean = sum(used) / len(used)
    return max(used) / mean if mean > 0 else None
