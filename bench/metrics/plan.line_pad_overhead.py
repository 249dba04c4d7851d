"""Planner: bucket lines x lanes over the real lines of those lanes, over
every study of the window (lane-weighted; 1.0 means no padding)."""


def read(run):
    real = sum(s.real_line_lanes for s in run.studies)
    if not real:
        return None
    return sum(s.padded_line_lanes for s in run.studies) / real
