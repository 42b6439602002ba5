"""Grouped-query attention with RoPE, sliding windows, cross-attention and
single-token decode against a KV cache.

The quadratic reference path lives here (and doubles as the oracle for the
Pallas flash kernel in ``repro/kernels``).  ``use_flash`` switches the train/
prefill path to the kernel.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.lm.common import (Params, ambient_abstract_mesh,
                                    apply_rope, shard_hint,
                                    truncated_normal_init)


def _qkv_hints(q, k, v):
    """Megatron-style activation sharding: heads over ``model`` where
    divisible; K/V with few KV heads replicate over ``model`` (cheap —
    they are 1/rep the size) so the score contraction is never sharded
    (a sharded-hd contraction would psum O(T*S) score tensors).

    When the *query* head count does not divide the model axis (phi3's
    40 heads on a 16-way axis) fall back to CONTEXT PARALLELISM: shard
    the query sequence dim over ``model`` instead — each shard computes
    its query rows against the full K/V, so attention compute/score
    memory still split model_size-ways (without this the whole attention
    runs replicated: measured 16x redundant FLOPs on phi3 prefill_32k)."""
    mesh = ambient_abstract_mesh()
    model = mesh.shape.get("model", 1) if mesh is not None else 1
    heads_shardable = q.shape[2] % model == 0 and q.shape[2] >= model
    if heads_shardable or q.shape[1] == 1:
        q = shard_hint(q, ("pod", "data"), None, "model", None)
    else:
        q = shard_hint(q, ("pod", "data"), "model", None, None)
    k = shard_hint(k, ("pod", "data"), None, "model", None)
    v = shard_hint(v, ("pod", "data"), None, "model", None)
    return q, k, v


def init_attention(key: jax.Array, d_model: int, n_heads: int,
                   n_kv_heads: int, head_dim: int, dtype,
                   qkv_bias: bool = False, kv_dim: Optional[int] = None
                   ) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    kv_dim = kv_dim or d_model
    p = {
        "wq": truncated_normal_init(kq, (d_model, n_heads * head_dim), 1.0,
                                    dtype),
        "wk": truncated_normal_init(kk, (kv_dim, n_kv_heads * head_dim),
                                    1.0, dtype),
        "wv": truncated_normal_init(kv, (kv_dim, n_kv_heads * head_dim),
                                    1.0, dtype),
        "wo": truncated_normal_init(ko, (n_heads * head_dim, d_model), 1.0,
                                    dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(p: Params, x: jax.Array, kv_src: jax.Array, n_heads: int,
                 n_kv_heads: int, head_dim: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, T = x.shape[:2]
    S = kv_src.shape[1]
    q = x @ p["wq"]
    k = kv_src @ p["wk"]
    v = kv_src @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, T, n_heads, head_dim),
            k.reshape(B, S, n_kv_heads, head_dim),
            v.reshape(B, S, n_kv_heads, head_dim))


def mha(q: jax.Array, k: jax.Array, v: jax.Array,
        causal: bool, window: int = 0,
        q_offset: int | jax.Array = 0, block_q: int = 0,
        scale: Optional[float] = None) -> jax.Array:
    """Reference attention.  q: [B,T,H,hd]; k/v: [B,S,KV,hd].

    ``window > 0`` = sliding-window (each query sees the previous ``window``
    keys inclusive).  ``q_offset`` is the absolute position of q[.,0] minus
    that of k[.,0] (for decode: S_cache).  ``block_q > 0`` switches to the
    memory-bounded blocked evaluation (scan over query blocks, rematerialized
    in backward) — required for the 4k/32k shape cells where the full
    ``[B, KV, T, rep, S]`` score tensor would not fit any memory.
    Scores are ``q k^T * scale``, by default ``hd ** -0.5``.
    """
    if block_q and q.shape[1] > block_q and q.shape[1] % block_q == 0:
        return _mha_blocked(q, k, v, causal=causal, window=window,
                            block_q=block_q, scale=scale)
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    rep = H // KV
    qf = q.astype(jnp.float32)
    qf = (qf / jnp.sqrt(hd).astype(jnp.float32) if scale is None
          else qf * scale).reshape(B, T, KV, rep, hd)
    kf = k.astype(jnp.float32)
    # grouped einsum: no materialized head-repeat of K/V
    logits = jnp.einsum("btkrh,bskh->bktrs", qf, kf)
    qpos = jnp.arange(T) + q_offset
    kpos = jnp.arange(S)
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bktrs,bskh->btkrh", probs, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


def _mha_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                 window: int, block_q: int,
                 scale: Optional[float] = None) -> jax.Array:
    """Scan over query blocks; each block takes a full softmax row against
    all of K/V (no online accumulation needed).  The block body is
    checkpointed so backward recomputes scores instead of storing them."""
    B, T, H, hd = q.shape
    nb = T // block_q
    qb = q.reshape(B, nb, block_q, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def body(_, xs):
        qi, i = xs
        # re-hint inside the scan body: the outer T-sharding dies when the
        # scan slices its block axis, so context parallelism must shard
        # the *within-block* query rows.
        qi, k2, v2 = _qkv_hints(qi, k, v)
        out = mha(qi, k2, v2, causal=causal, window=window,
                  q_offset=i * block_q, scale=scale)
        return None, out

    _, out = jax.lax.scan(body, None, (qb, jnp.arange(nb)))
    return out.swapaxes(0, 1).reshape(B, T, H, hd)


def self_attention(p: Params, x: jax.Array, *, n_heads: int,
                   n_kv_heads: int, head_dim: int, causal: bool,
                   rope_theta: float = 0.0, window: int = 0,
                   positions: Optional[jax.Array] = None,
                   use_flash: bool = False, block_q: int = 0,
                   scale: Optional[float] = None) -> jax.Array:
    """Self-attention of ``x`` [B, T, d_in] (``d_in`` is ``wq``'s input
    width); scores are ``q k^T * scale``, by default ``head_dim ** -0.5``."""
    B, T, _ = x.shape
    q, k, v = _project_qkv(p, x, x, n_heads, n_kv_heads, head_dim)
    q, k, v = _qkv_hints(q, k, v)
    if rope_theta > 0:
        pos = positions if positions is not None else jnp.arange(T)
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    if use_flash:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
    else:
        out = mha(q, k, v, causal=causal, window=window, block_q=block_q,
                  scale=scale)
    return out.reshape(B, T, n_heads * head_dim) @ p["wo"]


def cross_attention(p: Params, x: jax.Array, enc_out: jax.Array, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    block_q: int = 0) -> jax.Array:
    B, T, _ = x.shape
    q, k, v = _project_qkv(p, x, enc_out, n_heads, n_kv_heads, head_dim)
    q, k, v = _qkv_hints(q, k, v)
    out = mha(q, k, v, causal=False, block_q=block_q)
    return out.reshape(B, T, n_heads * head_dim) @ p["wo"]


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------

def decode_self_attention(p: Params, x: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, pos: jax.Array, *,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          rope_theta: float = 0.0, window: int = 0
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, 1, D]; cache_k/v: [B, S, KV, hd]; pos: scalar int32 (the
    absolute position being written).  Returns (out, new_k, new_v)."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, x, n_heads, n_kv_heads, head_dim)
    if rope_theta > 0:
        posv = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, posv, rope_theta)
        k = apply_rope(k, posv, rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), pos, axis=1)
    S, KV = cache_k.shape[1], cache_k.shape[2]
    rep = n_heads // KV
    qf = (q.astype(jnp.float32) / jnp.sqrt(head_dim).astype(jnp.float32)
          ).reshape(B, 1, KV, rep, head_dim)
    logits = jnp.einsum("btkrh,bskh->bktrs", qf,
                        cache_k.astype(jnp.float32))
    kpos = jnp.arange(S)
    valid = kpos <= pos
    if window > 0:
        valid &= kpos > pos - window
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bktrs,bskh->btkrh", probs,
                     cache_v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, n_heads * head_dim)
    return out @ p["wo"], cache_k, cache_v
