"""Mamba2 (SSD) block on top of the chunked GLA primitive.

Structure per block (pre-norm residual):
  in_proj -> [z | x | B | C | dt];  depthwise causal conv4 + silu on
  xBC = [x | B | C];  SSD recurrence per head (q=C, k=dt*B, v=x heads,
  decay=exp(-exp(A_log)*dt)), head ``h`` reading B and C of group
  ``h // (heads / n_groups)``;  skip D*x;  gate y*silu(z);  RMSNorm over
  each group's ``d_inner / n_groups`` channels;  out_proj.

Init (Mamba2's, as the Zamba2 modelling code keeps it): A drawn from
U(``A_INIT_RANGE``), ``A_log = log A``; dt log-uniform in ``DT_RANGE``,
floored at ``DT_FLOOR``, and ``dt_bias = softplus^-1(dt)``; ``D`` = 1.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.lm.common import (Params, rms_norm,
                                    truncated_normal_init)
from repro.models.lm.gla import chunked_gla, gla_decode_step


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2
    d_conv: int = 4
    chunk: int = 128
    n_groups: int = 1               # B/C groups; heads split evenly


# Mamba2's init (its A_init_range, dt_min, dt_max, dt_init_floor).
A_INIT_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4


def d_inner(d_model: int, cfg: SSMConfig) -> int:
    return cfg.expand * d_model


def n_ssm_heads(d_model: int, cfg: SSMConfig) -> int:
    return d_inner(d_model, cfg) // cfg.head_dim


def conv_channels(d_model: int, cfg: SSMConfig) -> int:
    """Channels of xBC, the conv's input: x, then B and C per group."""
    return d_inner(d_model, cfg) + 2 * cfg.n_groups * cfg.d_state


def init_A_log(key: jax.Array, nh: int) -> jax.Array:
    return jnp.log(jax.random.uniform(key, (nh,), jnp.float32,
                                      *A_INIT_RANGE))


def init_dt_bias(key: jax.Array, nh: int) -> jax.Array:
    lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
    dt = jnp.exp(jax.random.uniform(key, (nh,), jnp.float32, lo, hi))
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)


def init_mamba2(key: jax.Array, d_model: int, cfg: SSMConfig, dtype
                ) -> Params:
    di = d_inner(d_model, cfg)
    nh = n_ssm_heads(d_model, cfg)
    conv_ch = conv_channels(d_model, cfg)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "in_proj": truncated_normal_init(
            k1, (d_model, di + conv_ch + nh), 1.0, dtype),
        "conv_w": truncated_normal_init(k2, (cfg.d_conv, conv_ch), 1.0,
                                        dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": init_A_log(k3, nh),
        "dt_bias": init_dt_bias(k5, nh),
        "D_skip": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.zeros((di,), dtype),
        "out_proj": truncated_normal_init(k4, (di, d_model), 1.0, dtype),
    }


def _split_xbc(xBC: jax.Array, di: int, cfg: SSMConfig):
    """x [..., di], B and C [..., n_groups, d_state] of xBC."""
    gn = cfg.n_groups * cfg.d_state
    lead = xBC.shape[:-1]
    return (xBC[..., :di],
            xBC[..., di:di + gn].reshape(lead + (cfg.n_groups, cfg.d_state)),
            xBC[..., di + gn:].reshape(lead + (cfg.n_groups, cfg.d_state)))


def _per_head(g: jax.Array, nh: int) -> jax.Array:
    """[..., n_groups, N] -> [..., nh, N]: head ``h`` reads group
    ``h // (nh / n_groups)``."""
    return jnp.repeat(g, nh // g.shape[-2], axis=-2)


def gated_norm(y: jax.Array, z: jax.Array, w: jax.Array, n_groups: int,
               eps: float) -> jax.Array:
    """``y * silu(z)``, then RMSNorm over each of ``n_groups`` equal
    groups of channels."""
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    lead, di = y.shape[:-1], y.shape[-1]
    yg = y.reshape(lead + (n_groups, di // n_groups))
    wg = w.reshape(n_groups, di // n_groups)
    return rms_norm(yg, wg, eps).reshape(lead + (di,))


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 prev: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv along T.  x: [B,T,C]; w: [K,C]; prev: [B,K-1,C]
    carried state.  Returns (y [B,T,C], new_state [B,K-1,C])."""
    K = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)           # [B, T+K-1, C]
    # depthwise conv as a sum of shifted scalings (K is tiny, e.g. 4)
    T = x.shape[1]
    y = sum(xp[:, i:i + T, :] * w[i][None, None, :] for i in range(K))
    return y + b, xp[:, -(K - 1):, :] if K > 1 else \
        jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)


def _mamba2_forward(p: Params, x: jax.Array, cfg: SSMConfig,
                    conv_prev: Optional[jax.Array] = None,
                    use_kernel: bool = False, eps: float = 1e-6
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared fwd path.  Returns (y, new_conv_state, final_S)."""
    B, T, D = x.shape
    di = d_inner(D, cfg)
    nh = di // cfg.head_dim
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + conv_channels(D, cfg)]
    dt_pre = zxbcdt[..., -nh:].astype(jnp.float32)
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                                 prev=conv_prev)
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    xin, Bmat, Cmat = _split_xbc(xBC, di, cfg)
    dt = jax.nn.softplus(dt_pre + p["dt_bias"])               # [B,T,nh]
    log_decay = -jnp.exp(p["A_log"])[None, None, :] * dt      # [B,T,nh]

    v = xin.reshape(B, T, nh, cfg.head_dim)
    k = (_per_head(Bmat, nh) * dt[..., None]).astype(x.dtype)
    q = _per_head(Cmat, nh)
    with obs.scope("ssd"):
        y, (S_fin, _) = chunked_gla(q, k, v, log_decay, chunk=cfg.chunk,
                                    use_kernel=use_kernel)
    y = y + v * p["D_skip"][None, None, :, None].astype(v.dtype)
    y = gated_norm(y.reshape(B, T, di), z, p["norm_w"], cfg.n_groups, eps)
    return y @ p["out_proj"], new_conv, S_fin


def apply_mamba2(p: Params, x: jax.Array, cfg: SSMConfig,
                 use_kernel: bool = False, eps: float = 1e-6) -> jax.Array:
    """x: [B, T, D] -> [B, T, D] (training path)."""
    y, _, _ = _mamba2_forward(p, x, cfg, use_kernel=use_kernel, eps=eps)
    return y


def prefill_mamba2(p: Params, x: jax.Array, cfg: SSMConfig,
                   use_kernel: bool = False, eps: float = 1e-6
                   ) -> Tuple[jax.Array, Params]:
    """Prefill path: also return the recurrent cache for decode."""
    y, conv, S = _mamba2_forward(p, x, cfg, use_kernel=use_kernel, eps=eps)
    return y, {"conv": conv, "S": S}


def init_mamba2_cache(batch: int, d_model: int, cfg: SSMConfig, dtype
                      ) -> Params:
    di = d_inner(d_model, cfg)
    nh = di // cfg.head_dim
    conv_ch = conv_channels(d_model, cfg)
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, conv_ch), dtype),
        "S": jnp.zeros((batch, nh, cfg.d_state, cfg.head_dim),
                       jnp.float32),
    }


def decode_mamba2(p: Params, x: jax.Array, cache: Params, cfg: SSMConfig,
                  eps: float = 1e-6) -> Tuple[jax.Array, Params]:
    """x: [B, 1, D] single-token step with recurrent state."""
    B, _, D = x.shape
    di = d_inner(D, cfg)
    nh = di // cfg.head_dim
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + conv_channels(D, cfg)]
    dt_pre = zxbcdt[..., -nh:].astype(jnp.float32)
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                                 prev=cache["conv"])
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    xin, Bmat, Cmat = _split_xbc(xBC[:, 0], di, cfg)
    dt = jax.nn.softplus(dt_pre + p["dt_bias"])[:, 0]          # [B,nh]
    log_decay = -jnp.exp(p["A_log"])[None, :] * dt

    v = xin.reshape(B, nh, cfg.head_dim)
    k = (_per_head(Bmat, nh) * dt[..., None]).astype(x.dtype)
    q = _per_head(Cmat, nh)
    n_dummy = jnp.zeros((B, nh, cfg.d_state), jnp.float32)
    y, (S_new, _) = gla_decode_step(q, k, v, log_decay,
                                    (cache["S"], n_dummy))
    y = y + v * p["D_skip"][None, :, None].astype(v.dtype)
    y = gated_norm(y.reshape(B, 1, di), z, p["norm_w"], cfg.n_groups, eps)
    return y @ p["out_proj"], {"conv": new_conv, "S": S_new}
