"""Jit'd wrapper: Pallas flash attention on TPU, chunked-jnp oracle elsewhere.

``interpret`` is the caller's to set; it is never inferred from the
backend."""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _pallas
from repro.kernels.flash_attention import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                             "interpret"))
def mha(q, k, v, *, causal: bool = True, window: int = 0,
        use_pallas: bool | None = None, interpret: bool = False):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _pallas.flash_attention_pallas(
            q, k, v, causal=causal, window=window, interpret=interpret)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
