"""Chunked gated linear recurrence ("GLA/SSD" primitive).

One primitive covers both Mamba2's SSD and xLSTM's mLSTM:

    S_t = exp(a_t) * S_{t-1} + k_t^T v_t          (state  [dk, dv])
    n_t = exp(a_t) * n_{t-1} + k_t                (normalizer, optional)
    y_t = q_t @ S_t  [ / max(|q_t @ n_t|, 1) ]

with ``a_t <= 0`` log-decay.  Input gates are folded into ``k`` by the
caller.  The chunked evaluation is linear in sequence length: quadratic
*within* a chunk (MXU-friendly ``W x W`` matmuls), recurrent *across*
chunks (lax.scan).  This file is the pure-jnp reference; the Pallas kernel
in ``repro/kernels/gla_scan.py`` implements the same contract.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def chunked_gla(q: jax.Array, k: jax.Array, v: jax.Array,
                log_decay: jax.Array, *, chunk: int = 128,
                normalize: bool = False,
                initial_state: Optional[Tuple[jax.Array, jax.Array]] = None,
                use_kernel: bool = False
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """q,k: [B,T,H,dk]; v: [B,T,H,dv]; log_decay: [B,T,H] (<= 0, f32).

    Returns y: [B,T,H,dv] (dtype of v) and final (S: [B,H,dk,dv],
    n: [B,H,dk]).
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.gla_scan(q, k, v, log_decay, chunk=chunk,
                             normalize=normalize,
                             initial_state=initial_state)
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    W = min(chunk, T)
    if T % W:
        # pad to a chunk multiple with zero k/v and zero log-decay: padded
        # steps leave the state untouched and their outputs are dropped.
        pad = W - T % W
        padt = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2))
        y, state = chunked_gla(
            padt(q), padt(k), padt(v), padt(log_decay), chunk=W,
            normalize=normalize, initial_state=initial_state)
        return y[:, :T], state
    nc = T // W

    qf = q.astype(jnp.float32).reshape(B, nc, W, H, dk)
    kf = k.astype(jnp.float32).reshape(B, nc, W, H, dk)
    vf = v.astype(jnp.float32).reshape(B, nc, W, H, dv)
    af = log_decay.astype(jnp.float32).reshape(B, nc, W, H)
    ca = jnp.cumsum(af, axis=2)                      # [B,nc,W,H]
    tot = ca[:, :, -1, :]                            # [B,nc,H]

    # Intra-chunk quadratic term (per chunk, all chunks at once).
    # decay matrix D[i,j] = exp(ca_i - ca_j) for j <= i else 0.  The mask
    # goes on the exponent: above the diagonal ca_i - ca_j is positive
    # and can overflow exp, and 0 * inf is NaN in the backward.
    rel = ca[:, :, :, None, :] - ca[:, :, None, :, :]     # [B,nc,W,W,H]
    causal = jnp.tril(jnp.ones((W, W), bool))
    D = jnp.exp(jnp.where(causal[None, None, :, :, None], rel, -jnp.inf))
    scores = jnp.einsum("bcihd,bcjhd->bcijh", qf, kf) * D  # [B,nc,W,W,H]
    y_intra = jnp.einsum("bcijh,bcjhd->bcihd", scores, vf)

    # Per-chunk summaries for the cross-chunk recurrence.
    kd = kf * jnp.exp(tot[:, :, None, :, None] - ca[..., None])
    chunk_S = jnp.einsum("bcihk,bcihv->bchkv", kd, vf)    # [B,nc,H,dk,dv]
    chunk_n = jnp.einsum("bcihk->bchk", kd)               # [B,nc,H,dk]

    if initial_state is None:
        S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
        n0 = jnp.zeros((B, H, dk), jnp.float32)
    else:
        S0 = initial_state[0].astype(jnp.float32)
        n0 = initial_state[1].astype(jnp.float32)

    def body(carry, xs):
        S, n = carry
        cS, cn, decay_tot = xs              # [B,H,dk,dv],[B,H,dk],[B,H]
        newS = jnp.exp(decay_tot)[:, :, None, None] * S + cS
        newn = jnp.exp(decay_tot)[:, :, None] * n + cn
        return (newS, newn), (S, n)         # emit state *entering* chunk

    (Sf, nf), (S_in, n_in) = jax.lax.scan(
        body, (S0, n0),
        (chunk_S.swapaxes(0, 1), chunk_n.swapaxes(0, 1),
         tot.swapaxes(0, 1)))
    S_in = S_in.swapaxes(0, 1)              # [B,nc,H,dk,dv]
    n_in = n_in.swapaxes(0, 1)              # [B,nc,H,dk]

    q_dec = qf * jnp.exp(ca)[..., None]
    y_inter = jnp.einsum("bcihk,bchkv->bcihv", q_dec, S_in)
    y = y_intra + y_inter
    if normalize:
        denom_intra = jnp.einsum("bcijh,bcjhd->bcihd", scores,
                                 jnp.ones_like(vf[..., :1]))[..., 0]
        denom_inter = jnp.einsum("bcihk,bchk->bcih", q_dec, n_in)
        denom = jnp.abs(denom_intra + denom_inter)
        y = y / jnp.maximum(denom, 1.0)[..., None]
    return (y.reshape(B, T, H, dv).astype(v.dtype),
            (Sf, nf))


def gla_decode_step(q: jax.Array, k: jax.Array, v: jax.Array,
                    log_decay: jax.Array, state: Tuple[jax.Array, jax.Array],
                    *, normalize: bool = False
                    ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token recurrent step.  q,k: [B,H,dk]; v: [B,H,dv];
    log_decay: [B,H]; state: (S [B,H,dk,dv], n [B,H,dk])."""
    S, n = state
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    a = jnp.exp(log_decay.astype(jnp.float32))
    S = a[..., None, None] * S + kf[..., :, None] * vf[..., None, :]
    n = a[..., None] * n + kf
    y = jnp.einsum("bhk,bhkv->bhv", qf, S)
    if normalize:
        denom = jnp.abs(jnp.einsum("bhk,bhk->bh", qf, n))
        y = y / jnp.maximum(denom, 1.0)[..., None]
    return y.astype(v.dtype), (S, n)
