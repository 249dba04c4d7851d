"""Host spans and host<->device byte counters on the study path.

While a JAX profiler is recording (``jax.profiler.start_trace`` /
``jax.profiler.trace``), :class:`span` writes a ``repro:<name>`` event on
the calling host thread of the profiler's trace, with its keyword metadata
as event stats.  Every span also carries ``d2h_bytes`` and ``h2d_bytes``:
the bytes that :func:`d2h` read to the host and :func:`h2d` put on the
device while it was the innermost open span.  A span records host time
only; it waits for nothing on the device.

Each span closed while the profiler records is also kept in this process,
for a caller that reads its own spans without parsing the trace file:
:func:`recorded` returns them with their metadata and byte counters, timed on
``time.perf_counter``'s clock.

With no profiler recording, a span opens nothing, keeps nothing and the
counters count nothing; :func:`d2h` and :func:`h2d` still make their
transfer.
"""

from __future__ import annotations

import collections
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "repro:"
_local = threading.local()
# spans closed while a profiler recorded, newest last; about 25 per study
_recorded = collections.deque(maxlen=1 << 16)


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span("prepare"):`` or ``with span("run", study=3):``."""

    __slots__ = ("name", "meta", "d2h_bytes", "h2d_bytes", "seen", "_ann",
                 "_t0")

    def __init__(self, name: str, **meta):
        self.name, self.meta = name, meta
        self._ann = None

    def __enter__(self):
        if jax.profiler.TraceAnnotation.is_enabled():
            self.d2h_bytes = self.h2d_bytes = 0
            self.seen = {}  # id -> array already counted by d2h in this span
            self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                     **self.meta)
            self._ann.__enter__()
            _stack().append(self)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            t1 = time.perf_counter()
            _stack().remove(self)
            self._ann.set_metadata(d2h_bytes=self.d2h_bytes,
                                   h2d_bytes=self.h2d_bytes)
            self._ann.__exit__(*exc)
            self._ann = self.seen = None
            _recorded.append((self._t0, t1, PREFIX + self.name,
                              dict(self.meta, d2h_bytes=self.d2h_bytes,
                                   h2d_bytes=self.h2d_bytes)))
        return False


def recorded() -> list:
    """The spans closed while a profiler recorded, in the order they closed,
    as ``(start_s, end_s, name, meta)``: ``time.perf_counter`` times, the
    ``repro:`` name, and the span's metadata with ``d2h_bytes`` and
    ``h2d_bytes``.  The newest 65,536 are kept."""
    return list(_recorded)


def d2h(x) -> np.ndarray:
    """``np.asarray(x)``; a ``jax.Array`` counts its bytes once per span
    (JAX keeps the host copy, so a second read moves nothing)."""
    stack = _stack()
    if stack and isinstance(x, jax.Array):
        top = stack[-1]
        if id(x) not in top.seen:
            top.seen[id(x)] = x
            top.d2h_bytes += x.nbytes
    return np.asarray(x)


def h2d(x, dtype=None, sharding=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, or with a ``sharding`` a
    ``jax.device_put`` that puts each shard on its own device; a host ``x``
    counts the bytes put on the devices, all shards together."""
    if sharding is None:
        out = jnp.asarray(x, dtype)
    else:
        out = jax.device_put(np.asarray(x, dtype), sharding)
    stack = _stack()
    if stack and not isinstance(x, jax.Array):
        stack[-1].h2d_bytes += out.nbytes
    return out
