"""Shared by the scan readers: device busy milliseconds inside the
benchmark's dispatch spans of some mechanisms, averaged over the chips the
cell uses, per 1000 real lane-windows of those mechanisms."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as TR  # noqa: E402


def ms_per_kwin(run, keep):
    tr = run.trace
    if tr is None or not tr.ops or not run.studies:
        return None
    spans = [s for s in tr.spans if s[2].startswith("bench:dispatch:")
             and keep(s[2][len("bench:dispatch:"):])]
    if not spans:
        return None
    lo, hi = run.trace_window
    devs = sorted(tr.ops)[:run.chips]
    busy = [TR.overlap(TR.device_busy(tr, d, lo, hi), spans) for d in devs]
    mechs = {s[2] for s in spans}
    kwin = sum(s.real_lane_windows for s in run.studies) * len(mechs) / 1000
    return sum(busy) / len(busy) / 1e6 / kwin
