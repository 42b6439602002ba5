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

Backward (:func:`gla_scan_bwd`, ``normalize=False`` only): one grid step
per head, whose whole sequence sits in VMEM (q, k, v, dy and dq, dk, dv,
double-buffered: ~15 MB at T=4096, d=64 in bf16, as a 64-wide row takes
128 lanes; the call sets its VMEM limit to match).  A forward sweep over
the chunks rebuilds the states entering them (``nc x dk x dv`` f32 in
VMEM scratch), then a reverse sweep carries the state's cotangent
``dS [dk, dv]`` (and the normalizer's, a row) as the forward carries
``S``.  It reads only ``ca_row`` and turns it into the column it needs
with one in-kernel transpose, and it writes the decay gradient in the
same row orientation, so no lane-replicated decay crosses HBM.
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


# ---------------------------------------------------------------------------
# Backward (normalize=False, zero initial state)
# ---------------------------------------------------------------------------

def _nt(a, b):
    """a @ b.T with an f32 result."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a.T @ b with an f32 result."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _gla_bwd_kernel(q_ref, k_ref, v_ref, dy_ref, car_ref, ds_ref, dn_ref,
                    dq_ref, dk_ref, dv_ref, dca_ref, S_scr, n_scr, *,
                    L: int, nc: int):
    f32 = jnp.float32
    W, dk = q_ref.shape[2:]
    dv = v_ref.shape[3]

    def decay(c):
        ca_r = car_ref[0, c, :1, :]                    # [1, W]  ca_j
        ca_l = jnp.broadcast_to(ca_r, (L, W)).T        # [W, L]  ca_i per row
        return ca_r, ca_l, ca_l[W - 1:, :]             # tot: [1, L]

    # Forward sweep: the state and normalizer entering each chunk, built
    # as the forward kernel builds them.
    def states(c, carry):
        S, n = carry
        S_scr[c] = S
        n_scr[c] = jnp.broadcast_to(n, (SUBLANES, dk))
        _, ca_l, tot = decay(c)
        kd = k_ref[0, c].astype(f32) * jnp.exp(tot - ca_l)[:, :1]
        g = jnp.exp(tot)
        return (g[:, :dv] * S + _tn(kd, v_ref[0, c].astype(f32)),
                g[:, :dk] * n + jnp.sum(kd, axis=0, keepdims=True))

    jax.lax.fori_loop(0, nc, states, (jnp.zeros((dk, dv), f32),
                                      jnp.zeros((1, dk), f32)))

    # Reverse sweep, carrying dL/dS [dk, dv] and dL/dn [1, dk] leaving
    # each chunk.
    def chunk(i, carry):
        dS, dn = carry
        c = nc - 1 - i
        q, k, v, dy = q_ref[0, c], k_ref[0, c], v_ref[0, c], dy_ref[0, c]
        qf, kf, vf, dyf = (t.astype(f32) for t in (q, k, v, dy))
        ca_r, ca_l, tot = decay(c)
        ca_c = ca_l[:, :1]                             # [W, 1]
        S_in = S_scr[c]                                # [dk, dv]
        n_in = n_scr[c][:1, :]                         # [1, dk]

        causal = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0) >= \
            jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
        D = jnp.where(causal, jnp.exp(ca_c - ca_r), 0.0)   # [W, W]
        qk = _nt(q, k)                                 # [W, W]
        dAD = _nt(dy, v) * D                           # dy v^T, masked, decayed
        P = dAD * qk

        q_dec = qf * jnp.exp(ca_c)                     # [W, dk]
        kdec = jnp.exp(tot - ca_l)[:, :1]              # [W, 1]  exp(tot - ca_j)
        dq_in = _nt(dyf, S_in)                         # [W, dk]  dy S_in^T
        dk_out = _nt(vf, dS) + dn                      # [W, dk]  v dS^T + dn
        dq = jax.lax.dot(dAD, kf) + jnp.exp(ca_c) * dq_in
        dk_ = _tn(dAD, qf) + kdec * dk_out
        dv_ = _tn(qk * D, dyf) + jax.lax.dot(kf * kdec, dS)

        # dL/dca: the decay matrix (rows +, columns -), the queries' decay
        # of S_in, the keys' decay to the chunk's end (-, and + at W-1 via
        # tot), and the decay exp(tot) of S_in and n_in (+ at W-1).
        key = jnp.sum(kf * kdec * dk_out, axis=1, keepdims=True)    # [W, 1]
        col = jnp.sum(P, axis=1, keepdims=True) + jnp.sum(
            q_dec * dq_in, axis=1, keepdims=True) - key             # [W, 1]
        carried = jnp.sum(jnp.broadcast_to(
            jnp.sum(S_in * dS, axis=1, keepdims=True), (dk, L)),
            axis=0, keepdims=True) + jnp.sum(n_in * dn)             # [1, L]
        g = jnp.exp(tot)
        dtot = jnp.sum(jnp.broadcast_to(key, (W, L)), axis=0,
                       keepdims=True) + g * carried                 # [1, L]
        last = jax.lax.broadcasted_iota(jnp.int32, (W, L), 0) == W - 1
        dca_l = jnp.broadcast_to(col, (W, L)) + jnp.where(last, dtot, 0.0)
        dca_ref[0, c] = dca_l.T[:SUBLANES, :] - jnp.sum(P, axis=0,
                                                        keepdims=True)
        dq_ref[0, c] = dq.astype(dq_ref.dtype)
        dk_ref[0, c] = dk_.astype(dk_ref.dtype)
        dv_ref[0, c] = dv_.astype(dv_ref.dtype)
        return g[:, :dv] * dS + _tn(q_dec, dyf), g[:, :dk] * dn

    jax.lax.fori_loop(0, nc, chunk, (ds_ref[0], dn_ref[0, :1, :]))


def gla_scan_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                 log_decay: jax.Array, dy: jax.Array, dS: jax.Array,
                 dn: jax.Array, *, chunk: int = 128,
                 interpret: bool = False):
    """Gradients of :func:`gla_scan_fwd` (``normalize=False``) for the
    cotangents ``dy [BH, T, dv]``, ``dS [BH, dk, dv]`` and ``dn [BH, dk]``
    of its three outputs.  Returns ``(dq, dk, dv, dlog_decay)`` in the
    dtypes of ``q``, ``k``, ``v`` and ``log_decay``.

    One Pallas call (``gla_scan_bwd``, grid ``(BH,)``): each step holds
    one head's whole sequence in VMEM, rebuilds the states entering its
    chunks in a forward sweep, then walks the chunks in reverse carrying
    ``dL/dS`` and ``dL/dn``.  Per chunk it emits dq, dk, dv and the
    gradient of the chunk-local cumulative log-decay ``ca`` in row
    orientation (``[BH, nc, 8, W]``, like ``ca_row``); the reverse cumsum
    that turns it into the log-decay's gradient is taken outside.
    """
    BH, T, dk = q.shape
    dv = v.shape[-1]
    W = min(chunk, T)
    assert T % W == 0, (T, W)
    nc = T // W
    ca = jnp.cumsum(log_decay.astype(jnp.float32).reshape(BH, nc, W),
                    axis=-1)                                  # [BH, nc, W]
    ca_row = jnp.broadcast_to(ca[:, :, None, :], (BH, nc, SUBLANES, W))
    lanes = lambda d: -(-d // LANES) * LANES
    L = lanes(max(dk, dv))
    chunks = lambda x: x.reshape(BH, nc, W, x.shape[-1])
    head = lambda b: (b, 0, 0, 0)
    # VMEM: each block double-buffered at its lane-padded width, the
    # scratch, and room for one chunk's W x W temporaries.
    carry = 4 * (dk * lanes(dv) + SUBLANES * lanes(dk))      # dS, dn; S, n
    seq = (T * lanes(dk) * 2 * (q.dtype.itemsize + k.dtype.itemsize)
           + T * lanes(dv) * (2 * v.dtype.itemsize + dy.dtype.itemsize)
           + 2 * nc * SUBLANES * lanes(W) * 4 + carry)
    vmem = 2 * seq + nc * carry + 32 * W * lanes(max(W, L)) * 4
    dq, dk_, dv_, dca = pl.pallas_call(
        functools.partial(_gla_bwd_kernel, L=L, nc=nc),
        name="gla_scan_bwd",
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, nc, W, dk), head),
            pl.BlockSpec((1, nc, W, dk), head),
            pl.BlockSpec((1, nc, W, dv), head),
            pl.BlockSpec((1, nc, W, dv), head),
            pl.BlockSpec((1, nc, SUBLANES, W), head),
            pl.BlockSpec((1, dk, dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, SUBLANES, dk), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nc, W, dk), head),
            pl.BlockSpec((1, nc, W, dk), head),
            pl.BlockSpec((1, nc, W, dv), head),
            pl.BlockSpec((1, nc, SUBLANES, W), head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, W, dk), q.dtype),
            jax.ShapeDtypeStruct((BH, nc, W, dk), k.dtype),
            jax.ShapeDtypeStruct((BH, nc, W, dv), v.dtype),
            jax.ShapeDtypeStruct((BH, nc, SUBLANES, W), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nc, dk, dv), jnp.float32),
            pltpu.VMEM((nc, SUBLANES, dk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(chunks(q), chunks(k), chunks(v), chunks(dy), ca_row,
      dS.astype(jnp.float32),
      jnp.broadcast_to(dn.astype(jnp.float32)[:, None, :],
                       (BH, SUBLANES, dk)))
    # ca_i = sum_{t <= i} a_t inside a chunk, so dL/da_t = sum_{i >= t}.
    dca = dca[:, :, 0, :]
    da = jnp.flip(jnp.cumsum(jnp.flip(dca, -1), axis=-1), -1)
    return (dq.reshape(BH, T, dk), dk_.reshape(BH, T, dk),
            dv_.reshape(BH, T, dv),
            da.reshape(BH, T).astype(log_decay.dtype))
