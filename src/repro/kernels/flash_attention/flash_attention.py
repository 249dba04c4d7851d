"""Pallas TPU flash attention (GQA, causal / sliding-window).

Design (TPU-native tiling):

* Grid = (batch x q-heads, Sq / BLOCK_Q, Sk / BLOCK_K); the KV axis is the
  innermost (sequential) grid dim, so the online-softmax running state
  (m, l, acc) lives in VMEM scratch across KV steps of one Q tile — the
  canonical Pallas accumulation pattern.
* BLOCK_Q x BLOCK_K = 128 x 128 score tiles feed the MXU with aligned
  matmul dims; the softmax runs on the VPU in fp32.
* GQA: the kernel receives K/V already head-grouped — the index_map selects
  the kv head for each q head (hq // group_size), so no materialized repeat.
* Causal/window masking is computed from block-relative iotas; fully-masked
  KV tiles short-circuit via jnp.where guards (numerically, not control
  flow — TPU grids are static).

Validated under ``interpret=True`` against ``ref.py`` over shape/dtype
sweeps in tests/test_kernel_flash_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)
BLOCK_Q = 128
BLOCK_K = 128


def _fa_kernel(q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int, block_q: int,
               block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (BQ, D)
    k = k_ref[0].astype(jnp.float32)  # (BK, D)
    v = v_ref[0].astype(jnp.float32)  # (BK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finish():
        out_ref[0] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(out_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0,
    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = d ** -0.5

    pq = (-sq) % block_q
    pk = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    # Pad positions are masked: q-pads produce garbage rows we slice off;
    # k-pads are masked by the causal test only when causal — for
    # non-causal, mask via window of valid positions handled by padding k
    # with NEG_INF-producing zeros is unsafe, so require causal or exact sk.
    assert causal or pk == 0, "non-causal path requires Sk % block_k == 0"
    sqp, skp = qp.shape[1], kp.shape[1]

    # (B, S, H, D) -> (B*H, S, D): flatten batch x head into the grid
    qf = jnp.moveaxis(qp, 2, 1).reshape(b * hq, sqp, d)
    kf = jnp.moveaxis(kp, 2, 1).reshape(b * hkv, skp, d)
    vf = jnp.moveaxis(vp, 2, 1).reshape(b * hkv, skp, d)

    n_q = sqp // block_q
    n_k = skp // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(b, hq, sqp, d)[:, :, :sq, :]
    return jnp.moveaxis(out, 1, 2)
