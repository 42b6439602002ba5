"""Flash-attention kernels (Pallas TPU): the forward and its backward.

Forward: online-softmax attention with explicit VMEM tiling.  Grid is
``(B*H, T/bq, S/bk)``; the last grid axis is the TPU's sequential minor
axis, so the running max / denominator / accumulator live in VMEM scratch
across the K sweep and the output block is written once at the final K
step.  GQA is handled in the BlockSpec ``index_map`` (query head ``h``
reads KV head ``h // rep`` — no materialized K/V repeat).

The kernel also emits the per-query log-sum-exp, from which the
backward recomputes each score tile (standard flash backward without
re-doing the online softmax).

Backward: two calls that visit only the (query block, key block) pairs
the mask can keep (``band``): under a causal mask the blocks on or
below the diagonal, under a sliding window only those that end after
the window's start.  ``flash_attention_bwd_dkv`` sweeps, per key block,
the query heads of its KV head and the query blocks of its band,
accumulating dK and dV in VMEM; ``flash_attention_bwd_dq`` sweeps, per
query block, the key blocks of its band, accumulating dQ.  A grid step
past a block's band re-uses the block already in VMEM (its index is
clamped, so no DMA is issued) and does nothing.  Only blocks that
straddle the diagonal or the window's edge build the iota mask.

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
import numpy as np
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
                        interpret: bool = False, scale=None):
    """q: [BH, T, hd] (head-major); k/v: [BKV, S, hd]; rep = BH//BKV heads
    per KV head; scores are ``q k^T * scale`` (default ``hd ** -0.5``).
    Returns (o [BH, T, hd], lse [BH, T])."""
    BH, T, hd = q.shape
    BKV, S, _ = k.shape
    assert BH % BKV == 0
    rep = BH // BKV
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nk = T // bq, S // bk
    if scale is None:
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


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def block_pairs(i, j, bq: int, bk: int, causal: bool, window: int):
    """Whether the mask keeps (some, every) query-key pair of query
    block ``i`` and key block ``j``.  Over the block the offset
    ``q - k`` takes every value in ``[lo, hi]``; the mask keeps
    ``q - k >= 0`` (causal) and ``q - k < window`` (window > 0).  Plain
    arithmetic, so ints, numpy arrays and traced scalars all work."""
    lo = i * bq - (j + 1) * bk + 1
    hi = (i + 1) * bq - 1 - j * bk
    some = every = True
    if causal:
        some, every = some & (hi >= 0), every & (lo >= 0)
    if window > 0:
        some, every = some & (lo < window), every & (hi < window)
    return some, every


def band(T: int, S: int, bq: int, bk: int, causal: bool,
         window: int) -> np.ndarray:
    """The score blocks the mask can keep: ``[T/bq, S/bk]`` bool.  A
    pure function of the call's shapes and mask.  The kept blocks of a
    row (and of a column) are contiguous."""
    nq, nk = T // bq, S // bk
    kept, _ = block_pairs(np.arange(nq)[:, None], np.arange(nk)[None, :],
                          bq, bk, causal, window)
    return np.broadcast_to(kept, (nq, nk))


def _spans(kept: np.ndarray):
    """Per row of ``kept``: its first kept column (0 where none) and how
    many it keeps, as int32 tables for the grid's index maps."""
    count = kept.sum(axis=1).astype(np.int32)
    first = np.where(count > 0, kept.argmax(axis=1), 0).astype(np.int32)
    return first, count


def _band_block(first, count, n, t):
    """Band step ``t`` of row ``n``: its block, held at the row's last
    kept block past the band's end, so those steps issue no DMA."""
    return first[n] + jnp.minimum(t, jnp.maximum(count[n] - 1, 0))


def _keep(qpos, kpos, causal: bool, window: int):
    keep = jnp.ones(qpos.shape, jnp.bool_)
    if causal:
        keep &= qpos >= kpos
    if window > 0:
        keep &= kpos > qpos - window
    return keep


def _on_kept(in_band, i, j, bq, bk, causal, window, step):
    """Run ``step(masked)`` on block (i, j) when it is in the band:
    without the iota mask where the mask keeps every pair of it."""
    _, every = block_pairs(i, j, bq, bk, causal, window)
    if every is True:
        pl.when(in_band)(lambda: step(False))
        return
    pl.when(in_band & every)(lambda: step(False))
    pl.when(in_band & jnp.logical_not(every))(lambda: step(True))


_NT = (((1,), (1,)), ((), ()))           # a @ b.T


def _dkv_kernel(first_ref, count_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                causal: bool, window: int, scale: float, rep: int):
    """Key block ``j`` of KV head ``b`` against query block ``i`` of
    query head ``b * rep + r``, in the transposed frame (scores
    ``[bk, bq]``), where the per-query log-sum-exp and ``delta`` are
    rows."""
    j, r, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    i = first_ref[j] + t

    @pl.when((r == 0) & (t == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(_keep(qpos, kpos, causal, window), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                    # [bk, bq]
        dv_scr[...] += jax.lax.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0])
        dk_scr[...] += jax.lax.dot(dst.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _on_kept(t < count_ref[j], i, j, bq, bk, causal, window, step)

    @pl.when((r == rep - 1) & (t == pl.num_programs(3) - 1))
    def _emit():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(first_ref, count_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, lse_scr, delta_scr, *,
               causal: bool, window: int, scale: float):
    """Query block ``i`` of query head ``b`` against key block ``j``,
    scores ``[bq, bk]``; the log-sum-exp and ``delta`` rows are turned
    into lane-wide columns once per query block."""
    i, t = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    j = first_ref[i] + t

    @pl.when(t == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = jnp.broadcast_to(lse_ref[0], (LANES, bq)).T
        delta_scr[...] = jnp.broadcast_to(delta_ref[0], (LANES, bq)).T

    def step(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_keep(qpos, kpos, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse_scr[:, :1])                  # [bq, bk]
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[:, :1])
        dq_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    _on_kept(t < count_ref[i], i, j, bq, bk, causal, window, step)

    @pl.when(t == pl.num_programs(2) - 1)
    def _emit():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool,
                        window: int = 0, block_q: int = 512,
                        block_k: int = 512, interpret: bool = False,
                        scale=None):
    """Gradients (dq, dk, dv) of :func:`flash_attention_fwd` (with the
    same ``scale``) at cotangent ``do`` [BH, T, hd], from its output
    ``o`` and log-sum-exp ``lse`` [BH, T].  Scores, probabilities and their gradients are f32; the
    MXU takes ``p`` and ``ds`` in the activations' dtype and
    accumulates in f32."""
    BH, T, hd = q.shape
    BKV, S, _ = k.shape
    rep = BH // BKV
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    nq, nk = T // bq, S // bk
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    kept = band(T, S, bq, bk, causal, window)
    q_first, q_count = _spans(kept.T)          # per key block
    k_first, k_count = _spans(kept)            # per query block
    q_depth = max(int(q_count.max()), 1)
    k_depth = max(int(k_count.max()), 1)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = (lse.reshape(BH, 1, T), delta.reshape(BH, 1, T))

    # dK, dV: grid (KV head, key block, query head of it, band step).
    q_spec = pl.BlockSpec(
        (1, bq, hd),
        lambda b, j, r, t, f, c: (b * rep + r, _band_block(f, c, j, t), 0))
    row_spec = pl.BlockSpec(
        (1, 1, bq),
        lambda b, j, r, t, f, c: (b * rep + r, 0, _band_block(f, c, j, t)))
    kv_spec = pl.BlockSpec((1, bk, hd), lambda b, j, r, t, f, c: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          scale=scale, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BKV, nk, rep, q_depth),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, hd), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(jnp.asarray(q_first), jnp.asarray(q_count), q, k, v, do, *rows)

    # dQ: grid (query head, query block, band step).
    qi_spec = pl.BlockSpec((1, bq, hd), lambda b, i, t, f, c: (b, i, 0))
    kj_spec = pl.BlockSpec(
        (1, bk, hd),
        lambda b, i, t, f, c: (b // rep, _band_block(f, c, i, t), 0))
    rowi_spec = pl.BlockSpec((1, 1, bq), lambda b, i, t, f, c: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nq, k_depth),
            in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, rowi_spec,
                      rowi_spec],
            out_specs=qi_spec,
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32),
                            pltpu.VMEM((bq, LANES), jnp.float32),
                            pltpu.VMEM((bq, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(jnp.asarray(k_first), jnp.asarray(k_count), q, k, v, do, *rows)
    return dq, dk, dv
