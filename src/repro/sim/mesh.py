"""Mesh-sharded lane dispatch: shard the stacked lane axis over devices.

Every batched engine in this repo folds its whole cross-product onto ONE
stacked lane axis (:mod:`repro.sim.study`), and lanes are embarrassingly
parallel — no mechanism scan communicates across lanes.  This module is
the thin policy layer that spreads that axis over a 1-D ``lanes`` device
mesh (:func:`repro.launch.mesh.make_lane_mesh`) via ``shard_map``, with
three invariants the planner and the serve layer lean on:

* **The single-device path is byte-identical.**  ``devices=1`` selects
  the exact pre-mesh jitted functions (``engine._sweep_fn`` — the same
  callable objects, not equivalents), so it stays the differential
  reference the sharded path is pinned bit-exact against
  (``tests/test_mesh_dispatch.py``).
* **Mesh widths compose with the compile-key space.**  A sharded dispatch
  needs its lane count divisible by the mesh size, so buckets pad up to
  :func:`mesh_lane_width` with all-sentinel masked lanes
  (:func:`repro.sim.prep.dummy_trace` — zero contribution by the
  window-validity masking, the same mechanism ``pad_trace`` and the
  coalescer's blessed-width pads use).  Mesh sizes are powers of two
  (:func:`devices_for`), so every blessed coalesce width >= the mesh size
  is already a mesh multiple — blessed widths stay the compile-key space
  (:mod:`repro.serve.coalesce`), mesh multiples are chosen from them.
* **Scarce-lane buckets route to device subsets.**  A bucket with fewer
  lanes than devices runs on the largest power-of-two subset its lanes
  fill (:func:`devices_for`) instead of padding a 1-lane dispatch out to
  the full mesh.

Simulated multi-device CPU runs force the device count *before* jax
initializes (``--xla_force_host_platform_device_count``; precedent in
``launch/dryrun.py``).  CI sets :data:`MESH_ENV_VAR` and this module
translates it into ``XLA_FLAGS`` at import time, which is early enough
for any entry point that imports the sim before touching a device.
"""

from __future__ import annotations

import functools
import os

MESH_ENV_VAR = "XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT"
_XLA_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count() -> None:
    """Translate :data:`MESH_ENV_VAR` into ``XLA_FLAGS`` (idempotent; a
    no-op when unset or already configured).  Must run before jax's first
    backend initialization — imported-module top level is the reliable
    place, so this runs at import below.  Deliberately NOT guarded by a
    device query: querying devices would itself initialize the backend
    and lock the count at 1."""
    n = os.environ.get(MESH_ENV_VAR)
    if not n or _XLA_FLAG in os.environ.get("XLA_FLAGS", ""):
        return
    os.environ["XLA_FLAGS"] = (
        f"{os.environ.get('XLA_FLAGS', '')} {_XLA_FLAG}={int(n)}".strip())


force_host_device_count()

import jax  # noqa: E402  (the env translation above must precede this)
from jax.sharding import PartitionSpec  # noqa: E402

from repro.launch.mesh import LANE_AXIS, make_lane_mesh  # noqa: E402

__all__ = [
    "LANE_AXIS", "MESH_ENV_VAR", "available_devices", "resolve_devices",
    "devices_for", "mesh_lane_width", "lane_mesh", "shard_lanes",
    "force_host_device_count",
]


def available_devices() -> int:
    """Visible device count (initializes the jax backend)."""
    return len(jax.devices())


def resolve_devices(devices: int | None = None) -> int:
    """Normalize a ``devices=`` argument: ``None`` means every visible
    device; explicit counts are validated against availability so a
    manifest or config written on a bigger host fails loudly here, not
    inside shard_map."""
    if devices is None:
        return available_devices()
    devices = int(devices)
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if devices > available_devices():
        raise ValueError(
            f"devices={devices} but only {available_devices()} visible "
            f"(CPU CI forces more via {MESH_ENV_VAR})")
    return devices


def devices_for(lanes: int, devices: int) -> int:
    """The mesh size a ``lanes``-wide dispatch actually runs on: the
    largest power of two <= min(lanes, devices).  Scarce-lane buckets
    route to a device subset instead of padding out to the full mesh
    (1 lane on 4 devices runs single-device, 3 lanes run on 2), and
    pow2-only sizes keep mesh widths inside the blessed pow2 compile-key
    space."""
    if lanes < 1:
        raise ValueError(f"devices_for needs lanes >= 1, got {lanes}")
    d = 1
    while d * 2 <= min(lanes, devices):
        d *= 2
    return d


def mesh_lane_width(lanes: int, devices: int) -> int:
    """The padded lane count of a sharded dispatch: the smallest multiple
    of ``devices`` >= ``lanes`` (shard_map needs the sharded axis evenly
    divisible).  The pad lanes are all-sentinel masked traces that
    contribute nothing — same validity mechanism as ``pad_trace``."""
    if devices < 1:
        raise ValueError(f"mesh_lane_width needs devices >= 1, got {devices}")
    return -(-lanes // devices) * devices


@functools.lru_cache(maxsize=None)
def lane_mesh(devices: int):
    """The (cached) 1-D ``lanes`` mesh over the first ``devices`` devices."""
    return make_lane_mesh(devices)


def shard_lanes(fn, devices: int):
    """Wrap a vmapped-over-lanes function so its leading lane axis shards
    over a ``devices``-wide lane mesh.  Every input/output tensor leaf
    carries the stacked lane axis first, so one ``PartitionSpec('lanes')``
    prefix covers the whole pytree; there is no cross-lane communication
    to replicate, each device just scans its lane shard.  ``check_vma``
    is off: the scans start their carries from constants, which the
    varying-manual-axes check rejects, and with no collective there is
    nothing for it to check."""
    spec = PartitionSpec(LANE_AXIS)
    return jax.shard_map(fn, mesh=lane_mesh(devices),
                         in_specs=spec, out_specs=spec, check_vma=False)
