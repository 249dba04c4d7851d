"""Serving examples: continuous token batching (deliverable b) and the
resilient resident study service.

    PYTHONPATH=src python examples/serve_batched.py

Part 1 drives the continuous-batching token loop.  Part 2 stands up a
:class:`repro.serve.StudyServer` with 25% injected chaos faults and shows
every fault class resolving explicitly — reject, retry-success, degrade to
the bit-exact sequential engine, or crash-then-warm-restart — with zero
wrong results.
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.serve import serve  # noqa: E402
from repro.serve import (  # noqa: E402
    ChaosConfig,
    ChaosMonkey,
    ServeConfig,
    StudyServer,
    fresh_serve_dir,
    make_storm,
    restart_server,
)

SMALL = dict(num_kernels=3, windows_per_kernel=2)
SPECS = [
    {"workloads": [{"app": "pagerank", "graph": "arxiv", "scale": 0.4,
                    **SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
    {"workloads": [{"app": "htap128", "scale": 0.004, **SMALL}],
     "mechanisms": ["cpu", "cg", "lazypim"], "threads": 16},
]


def token_demo():
    args = argparse.Namespace(arch="qwen3-4b", smoke=True, requests=6,
                              batch=3, max_new=8, max_len=48, seed=0)
    served = serve(args)
    for r in served:
        print(f"req {r.rid}: prompt {len(r.prompt)} toks -> "
              f"{len(r.out) - len(r.prompt)} new toks")
    assert len(served) == args.requests


def study_service_demo():
    cache_dir = str(fresh_serve_dir("demo"))
    monkey = ChaosMonkey(ChaosConfig(seed=2, fault_rate=0.25, hang_s=5.0))
    cfg = ServeConfig(default_deadline_s=120.0, heartbeat_timeout_s=2.0,
                      backoff_base_s=0.01, max_lanes=64,
                      cache_dir=cache_dir)
    server = StudyServer(cfg, chaos=monkey)
    monkey.clock = server.clock

    final = {}
    for spec in make_storm(monkey, 12, SPECS):
        out = server.submit(spec)
        if not isinstance(out, int):
            final[out.rid] = out
    for r in server.drain():
        final[r.rid] = r
    while server.crashed:
        print("worker crashed — restarting from the warm compile cache")
        server, replayed = restart_server(cfg, chaos=monkey)
        for r in [*replayed, *server.drain()]:
            final[r.rid] = r

    for rid in sorted(final):
        r = final[rid]
        mark = " (recovered after crash)" if r.restarted else ""
        print(f"study req {rid}: {r.status} engine={r.engine} "
              f"attempts={r.attempts}{mark}")
    assert all(r.status != "crashed" for r in final.values())
    print(f"chaos injected: {monkey.injected or 'nothing'}")


def main():
    print("== continuous token batching ==")
    token_demo()
    print("\n== resident study service under chaos ==")
    study_service_demo()


if __name__ == "__main__":
    main()
