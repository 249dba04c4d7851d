"""repro.capture — coherence-trace capture from the live model zoo.

The bridge between the repo's two halves: the serving/training stack
(paged KV caches, MoE routing, the LazyEmbed coherence protocol) and the
LazyPIM simulator.  Each adapter instruments real model execution —
hooking the integer index streams the models already compute for their
gathers and scatters, never changing the model math — and emits a
:class:`repro.sim.trace.WindowTrace` through three layers:

* **recorder**: per-step raw line streams from the live loop;
* **line-mapper** (:mod:`repro.capture.layout`): row/page/expert id →
  64 B cache-line ids under a declared region layout, padded to the
  batch engine's pow4 geometry buckets;
* **windower** (:mod:`repro.capture.recorder`): fixed-shape ``(W, A)``
  slot arrays with -1 sentinels, §5.4 insert cap honored, kernel
  boundaries marked.

Captured traces are first-class workloads: ``make_trace(app="capture/
kv_serve")`` (and friends) routes here, so they flow through ``Study``
(sequential and batched) and serve coalescing unchanged.  Every random decision is
counter-PRNG keyed on (model seed, request-mix seed) — the same seed
gives a bit-identical ``WindowTrace``.
"""

from __future__ import annotations

from repro.capture.kv_serve import KVServeConfig, capture_kv_serve
from repro.capture.lazy_embed import LazyEmbedConfig, capture_lazy_embed
from repro.capture.layout import LineLayout, Region
from repro.capture.moe_experts import MoEExpertsConfig, capture_moe_experts
from repro.capture.recorder import WindowRecorder
from repro.sim.trace import CAPTURE_APPS, WindowTrace

_ADAPTERS = {
    "capture/kv_serve": capture_kv_serve,
    "capture/moe_experts": capture_moe_experts,
    "capture/lazy_embed": capture_lazy_embed,
}
assert set(_ADAPTERS) == set(CAPTURE_APPS)

# Per-adapter cpu_reuse defaults (mirrors build_plan's per-family rule:
# the KV hot tail is re-read hardest, like the streaming family).
_CPU_REUSE = {"capture/kv_serve": 8.0,
              "capture/moe_experts": 6.0,
              "capture/lazy_embed": 6.0}


def capture_trace(app: str, threads: int = 16, seed: int = 0,
                  num_kernels: int = 24, windows_per_kernel: int = 3,
                  scale: float | None = None, cpu_reuse: float | None = None,
                  backend: str = "jax") -> WindowTrace:
    """``make_trace`` backend for ``capture/*`` apps.

    Mirrors the synthetic entry point's signature; ``backend`` is accepted
    for uniformity but both values run the single recorder implementation
    (capture is numpy-driven bookkeeping around live jit'd model steps —
    there is no second generator to diverge from).
    """
    if backend not in ("jax", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    fn = _ADAPTERS.get(app)
    if fn is None:
        raise ValueError(
            f"unknown capture spec {app!r} (know {sorted(_ADAPTERS)}); "
            f"capture workloads are named 'capture/<adapter>'")
    return fn(threads=threads, seed=seed, num_kernels=num_kernels,
              windows_per_kernel=windows_per_kernel,
              scale=1.0 if scale is None else scale,
              cpu_reuse=_CPU_REUSE[app] if cpu_reuse is None else cpu_reuse)


__all__ = [
    "CAPTURE_APPS", "KVServeConfig", "LazyEmbedConfig", "LineLayout",
    "MoEExpertsConfig", "Region", "WindowRecorder", "capture_kv_serve",
    "capture_lazy_embed", "capture_moe_experts", "capture_trace",
]
