"""Flash-attention forward kernel (Pallas TPU).

Online-softmax attention with explicit VMEM tiling.  Grid is
``(B*H, T/bq, S/bk)``; the last grid axis is the TPU's sequential minor
axis, so the running max / denominator / accumulator live in VMEM scratch
across the K sweep and the output block is written once at the final K
step.  GQA is handled in the BlockSpec ``index_map`` (query head ``h``
reads KV head ``h // rep`` — no materialized K/V repeat).

The kernel also emits the per-query log-sum-exp, which the pure-jnp
chunked backward in ``ops.py`` consumes (standard flash backward without
re-doing the online softmax).

Block sizes default to 512x512 (f32 working set per step:
``3 * 512 * hd + 512 * 512`` ~ 2.3 MB for hd=128, comfortably inside the
~16 MB v5e VMEM).  The MXU sees ``[bq, hd] @ [hd, bk]`` and
``[bq, bk] @ [bk, hd]`` contractions — all dims multiples of 128 for the
shapes this repo runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                causal: bool, window: int, scale: float, nk: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale           # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                   # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

    bq, bk = s.shape
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]                              # [bq, 1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # [bq, bk]
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                   # [bk, hd]
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _emit():
        l = l_scr[:, :1]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe)             # [bq, 1]
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, window: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """q: [BH, T, hd] (head-major); k/v: [BKV, S, hd]; rep = BH//BKV heads
    per KV head.  Returns (o [BH, T, hd], lse [BH, T])."""
    BH, T, hd = q.shape
    BKV, S, _ = k.shape
    assert BH % BKV == 0
    rep = BH // BKV
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nk = T // bq, S // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_fwd_kernel, causal=causal, window=window,
                               scale=scale, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, T, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse[:, :, 0]
