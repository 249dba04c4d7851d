"""Jit'd public wrappers for the Bloom-signature kernels.

On TPU the compiled Pallas path is the default; elsewhere the pure-jnp
oracle is.  ``use_pallas=None`` selects by backend.  ``interpret`` is the
caller's to set and is never inferred: a Pallas call off the chip without
``interpret=True`` fails rather than quietly running the interpreter.
"""

from __future__ import annotations

import functools

import jax

from repro.core.signatures import SignatureSpec
from repro.kernels.bloom import bloom as _pallas
from repro.kernels.bloom import ref as _ref

_STATIC = ("spec", "use_pallas", "interpret")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=_STATIC)
def bloom_insert(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    mask: jax.Array | None = None,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Insert addresses into a packed signature (num_words,) uint32."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _pallas.bloom_insert_pallas(
            spec, sig, addrs, mask, interpret=interpret
        )
    return _ref.bloom_insert_ref(spec, sig, addrs, mask)


@functools.partial(jax.jit, static_argnames=_STATIC)
def bloom_query(
    spec: SignatureSpec,
    sig: jax.Array,
    addrs: jax.Array,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Membership test -> (N,) bool."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _pallas.bloom_query_pallas(spec, sig, addrs, interpret=interpret)
    return _ref.bloom_query_ref(spec, sig, addrs)


@functools.partial(jax.jit, static_argnames=_STATIC)
def bloom_detect_conflicts(
    spec: SignatureSpec,
    sigs: jax.Array,
    addrs: jax.Array,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Fused hash + membership-across-groups + hit count.

    ``sigs``: (G, num_words) uint32 packed; ``addrs``: (N,) -> (N,) int32
    hit-group counts (conflict iff >= 2).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _pallas.bloom_detect_conflicts_pallas(
            spec, sigs, addrs, interpret=interpret
        )
    return _ref.bloom_detect_conflicts_ref(spec, sigs, addrs)


@functools.partial(jax.jit, static_argnames=_STATIC)
def bloom_intersect(
    spec: SignatureSpec,
    a: jax.Array,
    b: jax.Array,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Batched AND-prefilter (B, num_words) x2 -> (B,) bool."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _pallas.bloom_intersect_pallas(spec, a, b, interpret=interpret)
    return _ref.bloom_intersect_ref(spec, a, b)
