"""Pallas TPU kernel for the LazySync conflict-row merge.

The merge is a bandwidth-bound fused reduction over the group dim:
``base + sum_g (rows_g - base)`` masked by validity.  The kernel tiles the
(R, D) row block into VMEM (rows x 128-lane feature tiles, MXU-aligned),
keeps the whole group dim resident per tile (G is small, <= 16), and fuses
the subtract/accumulate/select so each row crosses HBM exactly once —
instead of G+1 separate passes for the unfused jnp version.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 128
BLOCK_D = 128


def _merge_kernel(rows_ref, base_ref, valid_ref, out_ref):
    rows = rows_ref[...].astype(jnp.float32)     # (G, BR, BD)
    base = base_ref[...].astype(jnp.float32)     # (BR, BD)
    valid = valid_ref[...]                       # (BR,)
    merged = base + jnp.sum(rows - base[None], axis=0)
    out_ref[...] = jnp.where(valid[:, None] > 0, merged, base)


def lazy_merge_pallas(rows: jax.Array, base: jax.Array, valid: jax.Array,
                      *, block_r: int = BLOCK_R, block_d: int = BLOCK_D,
                      interpret: bool = False) -> jax.Array:
    """rows: (G, R, D); base: (R, D); valid: (R,) -> (R, D) float32."""
    g, r, d = rows.shape
    pr = (-r) % block_r
    pd = (-d) % block_d
    if pr or pd:
        rows = jnp.pad(rows, ((0, 0), (0, pr), (0, pd)))
        base = jnp.pad(base, ((0, pr), (0, pd)))
        valid = jnp.pad(valid, (0, pr))
    rp, dp = rows.shape[1], rows.shape[2]
    out = pl.pallas_call(
        _merge_kernel,
        grid=(rp // block_r, dp // block_d),
        in_specs=[
            pl.BlockSpec((g, block_r, block_d), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_r, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_r,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((block_r, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, dp), jnp.float32),
        interpret=interpret,
    )(rows, base, valid.astype(jnp.int32))
    return out[:r, :d]
