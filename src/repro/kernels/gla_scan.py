"""Chunked gated-linear-recurrence kernel (Pallas TPU) — the SSD/mLSTM
primitive shared by Mamba2 and xLSTM.

Contract (matches ``repro.models.lm.gla.chunked_gla``)::

    S_t = exp(a_t) S_{t-1} + k_t^T v_t
    n_t = exp(a_t) n_{t-1} + k_t
    y_t = q_t S_t  [/ max(|q_t n_t|, 1)]

Grid is ``(B*H, T/W)`` — the chunk axis is the TPU's sequential minor
grid axis, so the running ``[dk, dv]`` state and the normalizer live in
VMEM scratch across chunks.  Within a chunk everything is a
``W x W`` / ``W x dk`` / ``W x dv`` matmul (MXU-shaped); the recurrence
only crosses chunks, which is exactly the paper-recommended TPU
adaptation of a GPU sequential-scan kernel: quadratic *inside* the VMEM
tile, linear *across* tiles.

Decay layout.  Mosaic has no lowering for a cumulative sum, and it
refuses to broadcast a value that is 1 wide in both the sublane and the
lane dimension.  So the chunk-local inclusive cumsum ``ca`` of the
log-decay is taken outside the kernel (O(T) per head) and enters at lane
width, in the two orientations the kernel needs:

* ``ca_col [BH, T, 128]`` — ``ca_i`` down the rows, replicated across
  lanes (row ``i`` scales query/key row ``i``);
* ``ca_row [BH, 8, T]`` — ``ca_j`` along the lanes, replicated over one
  sublane tile (column ``j`` of the ``W x W`` decay matrix).

The chunk total ``ca_{W-1}`` enters as one ``[8, max(128, dv)]`` tile
per chunk (``tot [BH, nc*8, TL]``); the state decay reads it as a
``[1, dv]`` row straight from the ref, because Mosaic also refuses to
slice a value that was broadcast across sublanes.  The normalizer ``n``
is carried as a ``[dk, 128]`` column (lane-replicated).

VMEM working set per step (f32): ``W*dk*2 + W*dv*2 + 3*W*W + dk*dv``
plus two ``W x 128`` decay tiles — for W=256, dk=dv=64 that is ~1 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def _gla_kernel(q_ref, k_ref, v_ref, cac_ref, car_ref, tot_ref, y_ref,
                s_out_ref, n_out_ref, S_scr, n_scr, *, normalize: bool,
                nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        S_scr[...] = jnp.zeros_like(S_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    q = q_ref[0].astype(jnp.float32)          # [W, dk]
    k = k_ref[0].astype(jnp.float32)          # [W, dk]
    v = v_ref[0].astype(jnp.float32)          # [W, dv]
    ca_l = cac_ref[0]                         # [W, LANES] ca_i
    ca_c = ca_l[:, :1]                        # [W, 1]
    ca_r = car_ref[0, :1, :]                  # [1, W]  ca_j
    tot = tot_ref[0, :1, :LANES]              # [1, LANES] chunk total
    W, dk = q.shape
    dv = v.shape[1]

    # --- intra-chunk quadratic term -----------------------------------
    rel = ca_c - ca_r                         # [W, W] = ca_i - ca_j
    causal = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    D = jnp.where(causal, jnp.exp(rel), 0.0)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * D
    y = jax.lax.dot(scores, v)                # [W, dv]

    # --- cross-chunk term via carried state ----------------------------
    S_in = S_scr[...]                         # [dk, dv]
    n_in = n_scr[...]                         # [dk, LANES] (lanes equal)
    q_dec = q * jnp.exp(ca_c)                 # [W, dk]
    y = y + jax.lax.dot(q_dec, S_in)

    if normalize:
        ones = jnp.ones((W, LANES), jnp.float32)
        denom = jax.lax.dot(scores, ones) + jax.lax.dot(q_dec, n_in)
        y = y / jnp.maximum(jnp.abs(denom[:, :1]), 1.0)

    y_ref[0] = y.astype(y_ref.dtype)

    # --- state update ---------------------------------------------------
    kd = k * jnp.exp(tot - ca_l)[:, :1]       # [W, dk]
    S_new = jnp.exp(tot_ref[0, :1, :dv]) * S_in + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())))      # [dk, dv]
    g_col = jnp.exp(jnp.broadcast_to(tot, (dk, LANES)))   # [dk, LANES]
    n_new = g_col * n_in + jax.lax.dot_general(
        kd, jnp.ones((W, LANES), jnp.float32),
        (((0,), (0,)), ((), ())))             # [dk, LANES] column sums
    S_scr[...] = S_new
    n_scr[...] = n_new

    @pl.when(ci == nc - 1)
    def _emit():
        s_out_ref[0] = S_new
        n_out_ref[0] = n_new


def gla_scan_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                 log_decay: jax.Array, *, chunk: int = 128,
                 normalize: bool = False, interpret: bool = False):
    """q/k: [BH, T, dk]; v: [BH, T, dv]; log_decay: [BH, T] (f32, <= 0).

    Returns (y [BH, T, dv], S [BH, dk, dv], n [BH, dk]).
    Initial state is zero (callers with a nonzero initial state use the
    jnp reference — prefill/decode paths never hit the kernel).
    """
    BH, T, dk = q.shape
    dv = v.shape[-1]
    W = min(chunk, T)
    assert T % W == 0, (T, W)
    nc = T // W
    ca = jnp.cumsum(log_decay.astype(jnp.float32).reshape(BH, nc, W),
                    axis=-1)                                  # [BH, nc, W]
    ca_col = jnp.broadcast_to(ca.reshape(BH, T, 1), (BH, T, LANES))
    ca_row = jnp.broadcast_to(ca.reshape(BH, 1, T), (BH, SUBLANES, T))
    TL = max(LANES, -(-dv // LANES) * LANES)
    tot = jnp.broadcast_to(ca[:, :, -1:, None], (BH, nc, SUBLANES, TL)
                           ).reshape(BH, nc * SUBLANES, TL)

    kernel = functools.partial(_gla_kernel, normalize=normalize, nc=nc)
    y, S, n = pl.pallas_call(
        kernel,
        name="gla_scan_fwd",
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, W, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, W, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, W, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, W, LANES), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, SUBLANES, W), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, SUBLANES, TL), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, W, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, dk, LANES), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, dv), v.dtype),
            jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((BH, dk, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((dk, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, ca_col, ca_row, tot)
    return y, S, n[:, :, 0]
