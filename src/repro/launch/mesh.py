"""Production mesh construction + logical-axis rules.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The single-pod mesh
is (data=16, model=16) = 256 chips; multi-pod adds a leading pod axis for
2 x 256 = 512 chips.  The ``pod`` axis composes with ``data`` for
FSDP+DP (batch and parameter sharding span both), so the same logical rules
serve both meshes.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

LANE_AXIS = "lanes"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the models place activations with with_sharding_constraint,
    # which refuses the Explicit axes jax.make_mesh gives by default.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_lane_mesh(num_devices: int):
    """A 1-D ``lanes`` mesh over the first ``num_devices`` local devices —
    the simulator's lane-sharding axis (:mod:`repro.sim.mesh` builds all of
    its meshes through here).  Lanes are embarrassingly parallel (no
    cross-lane collective in any mechanism scan), so the only logical rule
    a lane mesh needs is the leading stacked-lane dim -> ``lanes``."""
    if num_devices < 1:
        raise ValueError(f"make_lane_mesh needs num_devices >= 1, "
                         f"got {num_devices}")
    devices = jax.devices()
    if num_devices > len(devices):
        raise ValueError(
            f"make_lane_mesh: {num_devices} devices requested but only "
            f"{len(devices)} visible (force more with "
            f"--xla_force_host_platform_device_count on CPU)")
    return jax.sharding.Mesh(np.array(devices[:num_devices]), (LANE_AXIS,))


# Logical-axis -> mesh-axis rules.  Parameters FSDP-shard their embed dim
# over data (and pod); vocab/heads/mlp/experts shard over model (TP/EP);
# batch shards over (pod, data).
LOGICAL_RULES_SINGLE: dict[str, Any] = {
    "batch": ("data",),
    "embed": ("data",),
    "embed_table": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "rnn": ("model",),
    "kv_seq": ("model",),
    "seq_sp": ("model",),
}

LOGICAL_RULES_MULTI: dict[str, Any] = {
    **LOGICAL_RULES_SINGLE,
    "batch": ("pod", "data"),
    "embed": ("data",),        # FSDP within a pod; pod axis replicates params
}

# Fully-sharded variant for the largest configs: parameters also shard the
# embed dim over the pod axis (FSDP across pods; gathered through DCN).
LOGICAL_RULES_MULTI_FSDP_POD: dict[str, Any] = {
    **LOGICAL_RULES_MULTI,
    "embed": ("pod", "data"),
}


def rules_for(mesh, *, fsdp_pod: bool = False) -> dict[str, Any]:
    """Logical-axis rules for a production mesh.  ``fsdp_pod=True`` selects
    the fully-sharded variant (parameters FSDP over the pod axis too) and
    requires a multi-pod mesh — on a single-pod mesh there is no pod axis
    to shard over, so asking for it is a config error, not a silent
    fallback."""
    if "pod" not in mesh.axis_names:
        if fsdp_pod:
            raise ValueError(
                f"rules_for(fsdp_pod=True) needs a multi-pod mesh (a 'pod' "
                f"axis); this mesh has axes {tuple(mesh.axis_names)}")
        return LOGICAL_RULES_SINGLE
    return LOGICAL_RULES_MULTI_FSDP_POD if fsdp_pod else LOGICAL_RULES_MULTI
