"""repro.serve — the resilient resident study service.

A long-lived server wrapping the Study planner in a hardened request
loop: bounded-queue admission with load shedding, per-request deadlines,
retry with deterministic backoff, graceful degradation to the sequential
reference engine, crash-safe warm-compile recovery, and fault-isolated
cross-request lane coalescing (:mod:`repro.serve.coalesce`) with
bisection rollback and per-lane result integrity.  The deterministic
fault-injection harness lives in :mod:`repro.serve.chaos`.
"""

from repro.serve.chaos import (
    ALL_FAULT_CLASSES,
    COALESCE_FAULT_CLASSES,
    FAULT_CLASSES,
    ChaosConfig,
    ChaosMonkey,
    InjectedEngineError,
    SimulatedCrash,
    make_storm,
)
from repro.serve.clock import VirtualClock, WallClock
from repro.serve.coalesce import (
    BLESSED_LANE_WIDTHS,
    GroupKey,
    LaneSlice,
    audit_sample,
    blessed_width,
    group_key,
    group_warm_entries,
    stack_group,
)
from repro.serve.policy import (
    AdaptivePolicy,
    PolicyConfig,
    ServiceModel,
    Telemetry,
)
from repro.serve.queueing import BoundedQueue
from repro.serve.request import (
    CRASHED,
    FAILED,
    OK,
    OK_DEGRADED,
    QUARANTINED,
    REJECTED,
    REJECTED_MALFORMED,
    REJECTED_OVERLOAD,
    REJECTED_OVERSIZED,
    SERVED,
    TERMINAL,
    TIMEOUT,
    Response,
    StudyRequest,
    build_study,
)
from repro.serve.retry import RetryPolicy
from repro.serve.server import (
    WORKER,
    DeadlineExceeded,
    ServeConfig,
    StudyServer,
    restart_server,
)
from repro.serve.warm import (
    ManifestCorruptError,
    WarmCache,
    fresh_serve_dir,
)

__all__ = [
    "ALL_FAULT_CLASSES",
    "COALESCE_FAULT_CLASSES",
    "FAULT_CLASSES",
    "ChaosConfig",
    "ChaosMonkey",
    "InjectedEngineError",
    "SimulatedCrash",
    "make_storm",
    "VirtualClock",
    "WallClock",
    "BLESSED_LANE_WIDTHS",
    "GroupKey",
    "LaneSlice",
    "audit_sample",
    "blessed_width",
    "group_key",
    "group_warm_entries",
    "stack_group",
    "AdaptivePolicy",
    "PolicyConfig",
    "ServiceModel",
    "Telemetry",
    "BoundedQueue",
    "CRASHED",
    "FAILED",
    "OK",
    "OK_DEGRADED",
    "QUARANTINED",
    "REJECTED",
    "REJECTED_MALFORMED",
    "REJECTED_OVERLOAD",
    "REJECTED_OVERSIZED",
    "SERVED",
    "TERMINAL",
    "TIMEOUT",
    "Response",
    "StudyRequest",
    "build_study",
    "RetryPolicy",
    "WORKER",
    "DeadlineExceeded",
    "ServeConfig",
    "StudyServer",
    "restart_server",
    "ManifestCorruptError",
    "WarmCache",
    "fresh_serve_dir",
]
