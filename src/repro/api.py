"""The public experiment API, one import away:

    from repro.api import Study, grid, workload

    rows = Study(workloads=["pagerank-arxiv", "htap128"]).run() \\
        .pivot("workload", "mechanism", "speedup")

Everything here re-exports from the simulation stack:

* :class:`Study` / :func:`grid` / :func:`workload` — the declarative
  (workloads × hw × mechanisms × lazy) spec with its automatic execution
  planner (:mod:`repro.sim.study`).
* :class:`ResultSet` / :class:`StudyPoint` / :class:`StudyPlan` — tagged
  results and the predicted compile budget.
* :class:`HWParams`, :class:`LazyPIMConfig`, :class:`SignatureSpec` — the
  hardware / protocol / signature parameter spaces.
* The sequential reference engine (:func:`run_all`) and
  :func:`summarize`, for code that runs one prepared trace per point.
"""

from repro.core.coherence import LazyPIMConfig
from repro.core.mechanisms import SimResult
from repro.core.signatures import SignatureSpec
from repro.sim.costmodel import HWParams
from repro.sim.engine import (
    MECHANISMS,
    run_all,
    summarize,
    sweep_cache_sizes,
)
from repro.sim.prep import TraceTensors, prepare
from repro.sim.study import (
    Dispatch,
    HWGrid,
    ResultSet,
    Study,
    StudyPlan,
    StudyPoint,
    Workload,
    grid,
    workload,
)
from repro.sim.trace import all_workloads, make_trace

__all__ = [
    "Study", "StudyPlan", "StudyPoint", "ResultSet",
    "Workload", "workload", "HWGrid", "grid", "Dispatch",
    "HWParams", "LazyPIMConfig", "SignatureSpec",
    "SimResult", "TraceTensors", "MECHANISMS",
    "run_all", "summarize",
    "sweep_cache_sizes", "prepare", "make_trace", "all_workloads",
]
