"""Shared building blocks for the LM model zoo (pure JAX, functional).

Conventions:
* params are nested dicts of jnp arrays; layer stacks store params with a
  leading ``[L, ...]`` axis and run under ``lax.scan``.
* activations are ``[B, T, D]``; compute dtype is configurable (bf16 target),
  softmax/normalization statistics are always f32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def ambient_abstract_mesh():
    """The mesh currently in scope (``jax.set_mesh``), or ``None``."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard_hint(x: jax.Array, *axes) -> jax.Array:
    """``with_sharding_constraint`` that degrades to a no-op when no mesh
    is in scope (CPU smoke tests) or when an axis name is absent from the
    ambient mesh (single-pod vs multi-pod).  ``axes``: one entry per dim,
    each a mesh-axis name, a tuple of names, or None."""
    mesh = ambient_abstract_mesh()
    if mesh is None:
        return x
    from jax.sharding import PartitionSpec

    # axes in Manual mode (inside shard_map) cannot appear in constraints
    auto = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}

    def reduce(a, dim):
        """Keep the subset of axis names present in the mesh (and not
        manual); drop the entry if the product no longer divides ``dim``."""
        if a is None:
            return None
        names = tuple(n for n in (a if isinstance(a, tuple) else (a,))
                      if n in auto)
        if not names:
            return None
        prod = 1
        for n in names:
            prod *= mesh.shape[n]
        if dim % prod != 0 or dim < prod:
            return None
        return names if len(names) > 1 else names[0]

    spec = tuple(reduce(a, x.shape[i]) for i, a in enumerate(axes))
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


def truncated_normal_init(key: jax.Array, shape: Tuple[int, ...],
                          scale: float, dtype=jnp.bfloat16) -> jax.Array:
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6
             ) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: [B, T, H, hd]; positions: [T] or [B, T] (absolute)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # [hd/2]
    if positions.ndim == 1:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
        angles = angles[None, :, None, :]  # [1, T, 1, hd/2]
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs
        angles = angles[:, :, None, :]     # [B, T, 1, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings [length, dim] (f32)."""
    pos = np.arange(length)[:, None]
    inv = np.exp(-np.log(10000.0) * np.arange(0, dim, 2) / dim)[None, :]
    emb = np.zeros((length, dim), np.float32)
    emb[:, 0::2] = np.sin(pos * inv)
    emb[:, 1::2] = np.cos(pos * inv)
    return jnp.asarray(emb)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key: jax.Array, d_model: int, d_ff: int, dtype
                ) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": truncated_normal_init(k1, (d_model, d_ff), 1.0, dtype),
        "w_up": truncated_normal_init(k2, (d_model, d_ff), 1.0, dtype),
        "w_down": truncated_normal_init(k3, (d_ff, d_model), 1.0, dtype),
    }


def apply_swiglu(p: Params, x: jax.Array) -> jax.Array:
    g = jax.nn.silu((x @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return (g * (x @ p["w_up"])) @ p["w_down"]


def apply_geglu(p: Params, x: jax.Array) -> jax.Array:
    """Gated-GELU MLP (gemma-style); same param layout as SwiGLU."""
    g = jax.nn.gelu((x @ p["w_gate"]).astype(jnp.float32),
                    approximate=True).astype(x.dtype)
    return (g * (x @ p["w_up"])) @ p["w_down"]


def sinusoidal_position_at(pos: jax.Array, dim: int) -> jax.Array:
    """Single-position sinusoidal embedding [dim] (f32), traced-pos safe."""
    inv = jnp.exp(-jnp.log(10000.0) * jnp.arange(0, dim, 2,
                                                 dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32) * inv
    emb = jnp.zeros((dim,), jnp.float32)
    emb = emb.at[0::2].set(jnp.sin(ang)).at[1::2].set(jnp.cos(ang))
    return emb


def init_gelu_mlp(key: jax.Array, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "w_up": truncated_normal_init(k1, (d_model, d_ff), 1.0, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": truncated_normal_init(k2, (d_ff, d_model), 1.0, dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def apply_gelu_mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.gelu((x @ p["w_up"] + p["b_up"]).astype(jnp.float32),
                    approximate=True).astype(x.dtype)
    return h @ p["w_down"] + p["b_down"]


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def chunked_softmax_xent(hidden: jax.Array, lm_head: jax.Array,
                         labels: jax.Array, mask: Optional[jax.Array] = None,
                         chunk: int = 512) -> jax.Array:
    """Mean next-token cross-entropy without materializing [B, T, V] at once.

    hidden: [B, T, D] (already final-normed), lm_head: [D, V],
    labels: [B, T] int32, mask: [B, T] (1 = count).
    """
    B, T, D = hidden.shape
    if mask is None:
        mask = jnp.ones((B, T), jnp.float32)
    n_chunks = max(T // chunk, 1)
    cs = T // n_chunks
    h = hidden.reshape(B, n_chunks, cs, D).swapaxes(0, 1)
    y = labels.reshape(B, n_chunks, cs).swapaxes(0, 1)
    m = mask.reshape(B, n_chunks, cs).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        hc, yc, mc = xs
        logits = (hc @ lm_head).astype(jnp.float32)
        # keep the [B, chunk, V] chunk sharded: batch over DP, vocab TP.
        logits = shard_hint(logits, ("pod", "data"), None, "model")
        logz = jax.nn.logsumexp(logits, axis=-1)
        # One-hot select, not take_along_axis: exact (the other terms add
        # +0.0), and a gather along the vocab-sharded dim inside the
        # hier_sync shard_map aborts XLA's SPMD partitioner on jax 0.9.0.
        gold = (logits * jax.nn.one_hot(yc, logits.shape[-1],
                                        dtype=logits.dtype)).sum(-1)
        nll = (logz - gold) * mc
        return (carry[0] + nll.sum(), carry[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)),
                                 (h, y, m))
    return tot / jnp.maximum(cnt, 1.0)
