"""Expert-parallel (shard_map) MoE must match the sort-based dispatch on a
real multi-device mesh — numerics and gradients (the §Perf hillclimb
winner must not change semantics).

NOTE: runs in a subprocess with 8 forced host devices so the main test
process keeps its single-device view.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

# The 8-forced-host-device subprocess is minutes of honest work on a fast
# backend but can exceed any fixed budget on slow/emulated containers.  A
# budget overrun is an environment property, not a code regression, so it
# skips with the reason instead of hang-then-fail; raise the budget via
# REPRO_MOE_EP_TIMEOUT_S where the backend is known-slow but worth waiting
# for.
_TIMEOUT_S = float(os.environ.get("REPRO_MOE_EP_TIMEOUT_S", 420))

_SCRIPT = r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models.model import Model
from repro.models import common as C

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg0 = get_smoke_config('qwen2_moe_a2_7b')
cfg0 = dataclasses.replace(cfg0, moe=dataclasses.replace(cfg0.moe, capacity_factor=8.0))
tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg0.vocab_size)
outs = {}
for mode in ('sort', 'ep'):
    cfg = dataclasses.replace(cfg0, moe_dispatch=mode)
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    with C.sharding_ctx(mesh):
        outs[mode] = np.asarray(jax.jit(lambda pp, tt: m.apply(pp, tt)[0])(p, tokens), np.float32)
np.testing.assert_allclose(outs['sort'], outs['ep'], rtol=3e-2, atol=3e-2)

cfg = dataclasses.replace(cfg0, moe_dispatch='ep')
m = Model(cfg); p = m.init(jax.random.key(0))
with C.sharding_ctx(mesh):
    loss, grads = jax.jit(jax.value_and_grad(lambda pp: m.loss(pp, {
        'tokens': tokens, 'labels': tokens})))(p)
assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
           for g in jax.tree.leaves(grads))
print("EP_OK")
"""


_ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_ep_matches_sort_on_mesh():
    # The child keeps the parent's environment (JAX_PLATFORMS among it) and
    # only gains its 8 forced host devices and the source path.
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.pathsep.join(
               filter(None, [str(_ROOT / "src"),
                             os.environ.get("PYTHONPATH")]))}
    try:
        out = subprocess.run(
            [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
            env=env, cwd=_ROOT, timeout=_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        pytest.skip(
            f"moe EP subprocess exceeded {_TIMEOUT_S:.0f}s on this backend "
            f"(8 forced host devices); set REPRO_MOE_EP_TIMEOUT_S to raise "
            f"the budget")
    assert "EP_OK" in out.stdout, out.stderr[-2000:]
