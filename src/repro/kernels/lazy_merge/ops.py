"""Jit'd wrapper for the lazy_merge kernel (Pallas on TPU, oracle elsewhere).

``interpret`` is the caller's to set (tests off the chip pass
``interpret=True``); it is never inferred from the backend."""

from __future__ import annotations

import functools

import jax

from repro.kernels.lazy_merge import lazy_merge as _pallas
from repro.kernels.lazy_merge import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def lazy_merge(rows, base, valid, use_pallas: bool | None = None,
               interpret: bool = False):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _pallas.lazy_merge_pallas(rows, base, valid,
                                         interpret=interpret)
    return _ref.lazy_merge_ref(rows, base, valid)
