"""Shared by the program-span readers: the program's own ``repro:`` spans
(``repro.runtime.spans``) that start inside the traced window, reduced to
seconds or megabytes per completed study.

The program keeps each span it closes while the profiler records, timed on
``time.perf_counter``, the clock of the harness's study records, so the
window is the studies' own: from the earliest prep start to the latest
study end.  A program without ``repro.runtime.spans`` reads nothing."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import trace_reduce as TR  # noqa: E402

# A study record's start (its end less its prep and run times) falls a few
# microseconds after the study's first program span opens: the window opens
# this much earlier, far less than the set-up study that runs untraced
# before every traced window.
LEAD_S = 0.01


def recorded():
    """The program's closed spans, or [] where it keeps none."""
    mod = sys.modules.get("repro.runtime.spans")
    return mod.recorded() if mod is not None else []


def _window_spans(run):
    """The window's spans, as (start, end, name, meta) in nanoseconds."""
    if not run.studies:
        return []
    lo = min(r.t1 - r.prep_s - r.run_s for r in run.studies) - LEAD_S
    hi = max(r.t1 for r in run.studies)
    return sorted(((round(s * 1e9), round(e * 1e9), name, meta)
                   for s, e, name, meta in recorded() if lo <= s < hi),
                  key=lambda p: p[:3])


def self_s(run, name):
    """Self time of the spans named ``name`` (each span's duration less the
    union of the program spans nested in it), seconds per study."""
    spans = _window_spans(run)
    mine = [i for i, p in enumerate(spans) if p[2] == name]
    if not mine:
        return None
    total = 0
    for i in mine:
        s, e = spans[i][:2]
        inner = [p for j, p in enumerate(spans)
                 if j != i and s <= p[0] and p[1] <= e]
        nested = TR.union([p[0] for p in inner], [p[1] for p in inner])
        total += (e - s) - TR.total(nested)
    return total / 1e9 / len(run.studies)


def megabytes(run, key):
    """The ``key`` counter (``d2h_bytes`` or ``h2d_bytes``) summed over
    every program span, in MB (1e6 bytes) per study."""
    spans = _window_spans(run)
    if not spans:
        return None
    return sum(p[3].get(key, 0) for p in spans) / 1e6 / len(run.studies)
