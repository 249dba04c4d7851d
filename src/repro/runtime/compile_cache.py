"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``python -m
repro.launch.serve``, ``python -m benchmarks.run``): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
sets another directory; otherwise the cache lives at one fixed path inside
the checkout, ``.jax_cache/``.  A cache directory only hits when the next
run finds it again, so it is never temporary, per-process or per-server.
Library code never calls this: a process that does not call
:func:`setup_compile_cache` (the test suite) keeps JAX's default, no cache.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def setup_compile_cache() -> pathlib.Path:
    """Turn on JAX's persistent compilation cache and return its directory.

    Every compiled program qualifies (no minimum size or compile time), so
    a restarted server's warm-manifest replay deserializes each scan
    instead of compiling it again (:mod:`repro.serve.warm`)."""
    env = os.environ.get(ENV_VAR)
    if env:
        path = pathlib.Path(env)
    else:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
