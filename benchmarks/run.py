"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,...]
    PYTHONPATH=src python -m benchmarks.run --bench signatures

Prints each figure's CSV block plus the headline-claims summary from the
calibration harness (benchmarks.calibrate).  ``--bench`` runs a named
microbench suite (``signatures`` or ``engine``), each writing its
``BENCH_<name>.json`` at the repo root via the shared
``benchmarks.timing.write_bench_json`` helper.  JAX's persistent compile
cache is placed by :func:`repro.runtime.compile_cache.setup_compile_cache`.
"""

from __future__ import annotations

import argparse
import time

MODULES = (
    "fig7_speedup",          # also covers fig2 (same metric, full set)
    "fig8_scaling",
    "fig9_traffic",
    "fig10_traffic_scaling",
    "fig11_energy",
    "fig12_partial_commits",
    "fig13_signature_size",
    "kernel_bloom",
    "lazy_sync_collectives",
)

BENCHES = {
    "signatures": "bench_signatures",
    "engine": "bench_engine",
    "serve": "bench_serve",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--bench",
        default=None,
        choices=sorted(BENCHES),
        help="run a named microbench suite instead of the figure modules",
    )
    args = ap.parse_args()

    import importlib

    from repro.runtime.compile_cache import setup_compile_cache

    setup_compile_cache()

    if args.bench:
        name = BENCHES[args.bench]
        print(f"\n===== {name} =====")
        t0 = time.time()
        importlib.import_module(f"benchmarks.{name}").main()
        print(f"[{name}: {time.time()-t0:.0f}s]")
        return

    only = set(args.only.split(",")) if args.only else None
    for name in MODULES:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====")
        t0 = time.time()
        importlib.import_module(f"benchmarks.{name}").main()
        print(f"[{name}: {time.time()-t0:.0f}s]")


if __name__ == "__main__":
    main()
