"""Host prep: seconds per study in trace synthesis, ``prepare`` and
bucket padding (the span around ``Study.traces()`` and
``Study.bucket_lanes()``)."""


def read(run):
    if not run.studies:
        return None
    return sum(s.prep_s for s in run.studies) / len(run.studies)
