"""Crash-safe warm-compile recovery: persistent XLA cache + warm manifest.

The compile budget is planned and gated (``Study.plan`` == measured
``sweep_cache_sizes`` deltas), but a fresh process still pays it.  Two
layers make the budget a *per-machine* cost instead of a per-process one:

1. **Persistent XLA compilation cache** — the entry point places it
   (:func:`repro.runtime.compile_cache.setup_compile_cache`: the directory
   ``JAX_COMPILATION_CACHE_DIR`` names, else one fixed path in the
   checkout), so any re-trace of a known (geometry, spec, static-flag)
   scan deserializes the compiled executable instead of re-running XLA.
   The server's ``cache_dir`` holds only its journal and warm manifest.

2. **Warm manifest** — the compiled-scan *key space* is exactly the
   planner's (mechanism, bucket geometry, lane count, signature spec,
   static lazy flags) tuples.  :meth:`WarmCache.record` persists every
   tuple a served study touched to ``warm_manifest.json``;
   :meth:`WarmCache.warm_from_manifest` replays them on a dummy
   all-invalid trace of the same geometry, re-populating the in-process
   jit caches through the *same* ``engine._sweep_fn`` functions every
   study dispatches through (compiles hit the persistent disk cache, so
   the replay is cheap).  A restarted server therefore answers previously
   seen studies with **zero new scan compiles** — measurable with the
   existing :func:`repro.sim.engine.sweep_cache_sizes` counter and gated
   exactly like the fig7 compile budget
   (``benchmarks/check_budget.py`` / ``benchmarks/bench_serve.py``).

The dummy warm trace is all-sentinel (no valid access slots, every window
invalid), so warming executes each scan once over carry passthroughs —
same compiled signature as real traffic, near-zero simulated work, and it
can never pollute any result: warm dispatches produce nothing anyone
reads.
"""

from __future__ import annotations

import json
import pathlib
import shutil

import jax
import jax.numpy as jnp

from repro.core.signatures import SignatureSpec
from repro.runtime.compile_cache import CHECKOUT
from repro.sim import engine as _engine
from repro.sim import mesh as _mesh
from repro.sim.prep import dummy_lane_triple
from repro.sim.study import Study

MANIFEST_NAME = "warm_manifest.json"
MANIFEST_SCHEMA_VERSION = 1

_GEOMETRY_KEYS = ("num_lines", "num_windows", "num_kernels",
                  "pim_read_slots", "pim_write_slots",
                  "cpu_read_slots", "cpu_write_slots")
# Required row fields.  "devices" (the lane-mesh size the dispatch sharded
# over) is written by every current producer but deliberately NOT required:
# pre-mesh manifests must keep loading, defaulting to 1 device at replay.
_ENTRY_KEYS = frozenset((*_GEOMETRY_KEYS, "mechanism", "lanes", "spec",
                         "lazy_static"))


class ManifestCorruptError(ValueError):
    """The warm manifest on disk is truncated, corrupt, or from an
    incompatible schema version.  :meth:`WarmCache.load_manifest` raises
    this internally, then *quarantines* the bad file (renamed to
    ``warm_manifest.json.corrupt-N``) and rebuilds from empty — a torn
    write must cost the warm state, never wedge ``restart_server``."""


def fresh_serve_dir(name: str) -> pathlib.Path:
    """An emptied server ``cache_dir`` at a fixed path in the checkout
    (``.serve_cache/<name>``), for runs that must start with no journal to
    replay and no manifest to warm: the demos and the serve benchmark."""
    path = CHECKOUT / ".serve_cache" / name
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


def study_warm_entries(study: Study, devices: int = 1) -> list[dict]:
    """The planner tuples a study's batched execution compiles, read from
    :meth:`Study.plan`: one entry per (mechanism, geometry bucket) with the
    bucket's geometry, its padded lane count and lane-mesh size (the width
    the dispatch actually compiled at) and the static compile-key context
    (signature spec, static lazy flags).  JSON-able — this is the manifest
    row format."""
    static = _engine.lazy_static(study.lazy_points()[0])
    return [{
        **{k: int(b[k]) for k in _GEOMETRY_KEYS},
        "mechanism": m,
        "lanes": int(b["padded_lanes"]),
        "devices": int(b["devices"]),
        "spec": dict(b["spec"]),
        "lazy_static": dict(static),
    } for b in study.plan(devices).buckets if b["lanes"]
        for m in study.mechanisms]


def _entry_key(e: dict) -> str:
    return json.dumps(e, sort_keys=True)


def dummy_stacked(entry: dict):
    """Build the (stacked trace, stacked hw, stacked lazy) triple whose jit
    key equals a manifest entry's compile key: exact bucket geometry and
    lane count, every lane the pad triple of
    :func:`repro.sim.prep.dummy_lane_triple`, placed as
    :func:`repro.sim.engine.stack_lanes` places a dispatch on the entry's
    lane mesh (a different placement would be a different compile key).
    The trace is broadcast with jnp ops, so ``jax.eval_shape`` gives the
    entry's argument shapes without materializing any lane
    (``tests/test_tpu_compile.py``)."""
    tt, hw, cfg = dummy_lane_triple(
        SignatureSpec(**entry["spec"]),
        {k: entry[k] for k in _GEOMETRY_KEYS}, entry["lazy_static"])
    lanes = entry["lanes"]
    sharding = _mesh.lane_sharding(int(entry.get("devices", 1)))
    stt = jax.tree.map(lambda x: jnp.broadcast_to(x, (lanes, *x.shape)), tt)
    if sharding is not None:
        stt = jax.device_put(stt, sharding)
    return (stt, _engine.stack_hw([hw] * lanes, sharding),
            _engine.stack_lazy([cfg] * lanes, sharding))


class WarmCache:
    """The server's crash-safe warm state: manifest bookkeeping + replay."""

    def __init__(self, cache_dir: str | pathlib.Path):
        self.dir = pathlib.Path(cache_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.manifest_path = self.dir / MANIFEST_NAME
        self.quarantined_manifests = 0  # corrupt files set aside, not read
        self.skipped_entries = 0        # mesh entries this host cannot replay

    def _parse_manifest(self, text: str) -> list[dict]:
        """Strict manifest parse; any deviation is a named
        :class:`ManifestCorruptError` (the caller quarantines)."""
        try:
            payload = json.loads(text)
        except json.JSONDecodeError as e:
            raise ManifestCorruptError(
                f"{self.manifest_path}: not valid JSON (truncated or "
                f"corrupt write): {e}") from e
        if not isinstance(payload, dict) or "entries" not in payload:
            raise ManifestCorruptError(
                f"{self.manifest_path}: expected an object with an "
                f"'entries' list")
        # Pre-stamp manifests (written before the schema_version field
        # existed) are the version-1 entry layout; a missing field loads.
        version = payload.get("schema_version", MANIFEST_SCHEMA_VERSION)
        if version != MANIFEST_SCHEMA_VERSION:
            raise ManifestCorruptError(
                f"{self.manifest_path}: schema_version {version!r} "
                f"unsupported (this build reads "
                f"{MANIFEST_SCHEMA_VERSION})")
        entries = payload["entries"]
        if not isinstance(entries, list) or not all(
                isinstance(e, dict) and _ENTRY_KEYS <= set(e)
                for e in entries):
            raise ManifestCorruptError(
                f"{self.manifest_path}: malformed entry rows (want "
                f"{sorted(_ENTRY_KEYS)} per entry)")
        return entries

    def load_manifest(self) -> list[dict]:
        """Manifest entries, or ``[]``.  A corrupt/truncated/incompatible
        manifest is *quarantined* — renamed to ``warm_manifest.json
        .corrupt-N`` for diagnosis — and the warm state rebuilds from
        empty; ``restart_server`` must never wedge on a torn write."""
        if not self.manifest_path.exists():
            return []
        try:
            return self._parse_manifest(self.manifest_path.read_text())
        except ManifestCorruptError:
            n = 0
            while (q := self.manifest_path.with_name(
                    f"{MANIFEST_NAME}.corrupt-{n}")).exists():
                n += 1
            self.manifest_path.replace(q)
            self.quarantined_manifests += 1
            return []

    def record(self, study: Study, devices: int = 1) -> int:
        """Merge a served study's planner tuples into the manifest
        (idempotent; crash-safe via atomic rename).  Returns the number of
        new entries."""
        return self.record_entries(study_warm_entries(study, devices))

    def record_entries(self, new_entries: list[dict]) -> int:
        """Merge compile-key entry rows into the manifest — the shared
        write path for per-study tuples (:meth:`record`) and the
        coalescer's blessed-width group tuples
        (:func:`repro.serve.coalesce.group_warm_entries`)."""
        entries = self.load_manifest()
        seen = {_entry_key(e) for e in entries}
        fresh = [e for e in new_entries if _entry_key(e) not in seen]
        if fresh:
            tmp = self.manifest_path.with_suffix(".tmp")
            tmp.write_text(json.dumps(
                {"schema_version": MANIFEST_SCHEMA_VERSION,
                 "entries": entries + fresh}, indent=2) + "\n")
            tmp.replace(self.manifest_path)
        return len(fresh)

    def warm(self, entries: list[dict]) -> int:
        """Replay manifest entries through the engine's own sweep functions
        so the in-process jit caches hold every recorded compile key (XLA
        compiles hit the persistent disk cache when enabled).  Returns the
        number of dispatches replayed.

        Entries recorded on a wider mesh than this host has (``devices`` >
        visible devices — a manifest carried over from a bigger machine)
        are *skipped*, counted in :attr:`skipped_entries`: live traffic
        rebuilds its own compile keys at this host's routing, which is the
        correct warm state here — a replay must never wedge the restart."""
        avail = _mesh.available_devices()
        replayed = 0
        for e in entries:
            d = int(e.get("devices", 1))
            if d > avail:
                self.skipped_entries += 1
                continue
            stt, shw, scfg = dummy_stacked(e)
            m = e["mechanism"]
            fn = _engine._sweep_fn_mesh(m, d)
            acc = fn(stt, shw, scfg) if m == "lazypim" else fn(stt, shw)
            jax.block_until_ready(acc)
            replayed += 1
        return replayed

    def warm_from_manifest(self) -> int:
        return self.warm(self.load_manifest())
