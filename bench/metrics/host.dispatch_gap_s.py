"""Dispatch: seconds per study of ``Study.run`` outside its compiled-scan
dispatches (stacking, device puts, result split and finalize)."""


def read(run):
    if not run.studies:
        return None
    gaps = [s.run_s - sum(w for _, w in s.dispatch) for s in run.studies]
    return sum(gaps) / len(gaps)
