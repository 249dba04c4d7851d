"""Batched serving driver: continuous-batching loop over a request queue.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \\
        --requests 8 --max-new 32

Implements the serving-side substrate: a request queue, batched prefill
(left-padded to the batch's max prompt), then lockstep batched decode with
per-request stop handling; finished slots are refilled from the queue
(continuous batching).  On a pod the same step functions run under pjit
with the decode-cache shardings from ``launch.steps``.

``--study`` switches the driver to the resident *study* service
(:mod:`repro.serve`): read a JSON file holding one study-request spec (or
a list of them), answer each through the hardened request loop — retries,
degradation, crash-safe restart — and print one status line per request::

    PYTHONPATH=src python -m repro.launch.serve --study requests.json \\
        --cache-dir .serve_cache [--chaos-rate 0.1] [--deadline-s 300]

With ``--cache-dir`` the server journals admitted requests and keeps the
warm manifest there; JAX's persistent compile cache sits where
:func:`repro.runtime.compile_cache.setup_compile_cache` puts it, so a
re-launch answers repeat studies without recompiling a single scan.  The
process exits non-zero when any request ends ``failed``, or ``crashed``
with no restart to answer it.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

# Simulated multi-device lane meshes: repro.sim.mesh translates
# XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT into XLA_FLAGS at import time, which
# must happen before jax's first backend init — so it is imported first
# (same constraint as the XLA_FLAGS line atop launch/dryrun.py).
import repro.sim.mesh  # noqa: F401  isort: skip

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models.model import Model
from repro.runtime.compile_cache import setup_compile_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


def make_requests(cfg, n: int, seed: int = 0, max_new: int = 32):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 24))
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
            max_new=max_new))
    return reqs


def serve(args) -> list[Request]:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    assert cfg.encoder_layers == 0 and cfg.frontend is None, \
        "serve driver targets decoder-only text archs"
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    decode = jax.jit(model.decode)

    queue = make_requests(cfg, args.requests, args.seed, args.max_new)
    batch = args.batch
    max_len = args.max_len

    # continuous batching state
    slots: list[Request | None] = [None] * batch
    cache = model.init_cache(batch, max_len)
    # one shared cache: per-slot "position" handled by feeding tokens in
    # lockstep; empty slots decode a pad token and are ignored.
    t0 = time.time()
    served = []
    pending = list(queue)
    cur_tok = jnp.zeros((batch, 1), jnp.int32)

    def refill():
        nonlocal cur_tok
        for s in range(batch):
            if slots[s] is None and pending:
                slots[s] = pending.pop(0)

    refill()
    # teacher-forced "prefill" through the decode path keeps one jitted
    # program resident (one-token steps; prompts are short in this driver)
    steps = 0
    while any(s is not None for s in slots) :
        feed = np.zeros((batch, 1), np.int32)
        for s, req in enumerate(slots):
            if req is None:
                continue
            consumed = len(req.out)
            if consumed < len(req.prompt):
                feed[s, 0] = req.prompt[consumed]
            elif req.out:
                feed[s, 0] = req.out[-1] % cfg.vocab_size
        logits, cache = decode(params, jnp.asarray(feed), cache)
        steps += 1
        nxt = np.asarray(jnp.argmax(logits[:, 0, : cfg.vocab_size], axis=-1))
        for s, req in enumerate(slots):
            if req is None:
                continue
            req.out.append(int(nxt[s]))
            new_tokens = len(req.out) - len(req.prompt)
            if new_tokens >= req.max_new or steps >= max_len - 1:
                req.done = True
                served.append(req)
                slots[s] = None
        refill()
        if steps >= max_len - 1:
            break

    dt = time.time() - t0
    total_toks = sum(len(r.out) for r in served)
    print(f"served {len(served)} requests, {total_toks} tokens, "
          f"{steps} batched steps in {dt:.1f}s "
          f"({total_toks/max(dt,1e-9):.1f} tok/s)")
    return served


def serve_study(args) -> list:
    """The resident study service: answer the request specs in
    ``args.study`` (a JSON file holding one spec dict or a list of them)
    through the hardened loop, restarting from the warm compile cache if
    the worker crashes.  Returns the terminal responses in rid order."""
    import json
    import pathlib

    from repro.serve import (ChaosConfig, ChaosMonkey, ServeConfig,
                             StudyServer, restart_server)

    specs = json.loads(pathlib.Path(args.study).read_text())
    if isinstance(specs, dict):
        specs = [specs]
    cfg = ServeConfig(default_deadline_s=args.deadline_s,
                      max_queue=args.max_queue, cache_dir=args.cache_dir,
                      seed=args.seed,
                      coalesce=args.coalesce or args.adaptive,
                      adaptive=args.adaptive)
    chaos = None
    if args.chaos_rate > 0:
        chaos = ChaosMonkey(ChaosConfig(seed=args.seed,
                                        fault_rate=args.chaos_rate))
    server = StudyServer(cfg, chaos=chaos)
    if chaos is not None:
        chaos.clock = server.clock
    final = {}
    for spec in specs:
        out = server.submit(spec)
        if not isinstance(out, int):
            final[out.rid] = out
    for r in server.drain():
        final[r.rid] = r
    while server.crashed:
        print("worker crashed — restarting from the warm compile cache")
        server, replayed = restart_server(cfg, chaos=chaos)
        for r in [*replayed, *server.drain()]:
            final[r.rid] = r
    for rid in sorted(final):
        r = final[rid]
        extra = f" ({r.error})" if r.error else ""
        print(f"req {rid}: {r.status} engine={r.engine} "
              f"attempts={r.attempts} {r.latency_s * 1e3:.0f} ms{extra}")
    counts: dict[str, int] = {}
    for r in final.values():
        counts[r.status] = counts.get(r.status, 0) + 1
    print(f"served {len(final)} requests: {counts}")
    if cfg.adaptive:
        t = server.telemetry.summary()
        print(f"policy: formation_holds={t['formation_holds']} "
              f"decisions={t['decisions']}")
    return [final[rid] for rid in sorted(final)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--study", default=None, metavar="SPECS_JSON",
                    help="serve study requests from this JSON file instead "
                         "of running the token-serving driver")
    ap.add_argument("--cache-dir", default=None,
                    help="journal + warm manifest directory (enables "
                         "crash-safe restart)")
    ap.add_argument("--deadline-s", type=float, default=300.0)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help="inject this fraction of chaos faults (testing)")
    ap.add_argument("--coalesce", action="store_true",
                    help="coalesce compatible queued studies into shared "
                         "blessed-width batched dispatches (bit-exact; "
                         "poison requests are bisected out and quarantined)")
    ap.add_argument("--adaptive", action="store_true",
                    help="adaptive coalescing policy (implies --coalesce): "
                         "slack-aware formation window under light load, "
                         "slack-driven batch width, repeat-offender group "
                         "keys routed to the sequential reference")
    args = ap.parse_args()
    setup_compile_cache()
    if args.study:
        from repro.serve import CRASHED, FAILED

        bad = [r.rid for r in serve_study(args)
               if r.status in (FAILED, CRASHED)]
        if bad:
            sys.exit(f"requests {bad} ended failed or crashed")
        return
    served = serve(args)
    assert len(served) == args.requests


if __name__ == "__main__":
    main()
