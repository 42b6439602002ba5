"""xLSTM blocks: mLSTM (matrix memory, chunked-parallel via GLA) and sLSTM
(scalar memory, strictly recurrent over time).

Simplifications vs arXiv:2405.04517, recorded in DESIGN.md: the mLSTM input
gate is clamped to [-8, 8] instead of carrying the running max-stabilizer
``m_t`` (the GLA normalizer bounds the output); the sLSTM keeps the standard
log-space stabilizer.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.lm.common import (Params, rms_norm,
                                    truncated_normal_init)
from repro.models.lm.gla import chunked_gla, gla_decode_step
from repro.models.lm.ssm import _causal_conv


@dataclasses.dataclass(frozen=True)
class XLSTMConfig:
    n_heads: int = 4
    expand: int = 2          # mLSTM inner expansion
    d_conv: int = 4
    slstm_every: int = 6     # every k-th block is an sLSTM (0 = never)
    chunk: int = 128


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def init_mlstm(key: jax.Array, d_model: int, cfg: XLSTMConfig, dtype
               ) -> Params:
    di = cfg.expand * d_model
    hd = di // cfg.n_heads
    ks = jax.random.split(key, 7)
    return {
        "up_proj": truncated_normal_init(ks[0], (d_model, 2 * di), 1.0,
                                         dtype),
        "conv_w": truncated_normal_init(ks[1], (cfg.d_conv, di), 1.0, dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "wq": truncated_normal_init(ks[2], (di, di), 1.0, dtype),
        "wk": truncated_normal_init(ks[3], (di, di), 1.0, dtype),
        "wv": truncated_normal_init(ks[4], (di, di), 1.0, dtype),
        "w_gates": truncated_normal_init(ks[5], (di, 2 * cfg.n_heads), 1.0,
                                         jnp.float32),
        "b_igate": jnp.zeros((cfg.n_heads,), jnp.float32),
        "b_fgate": jnp.full((cfg.n_heads,), 3.0, jnp.float32),
        "norm_w": jnp.zeros((di,), dtype),
        "down_proj": truncated_normal_init(ks[6], (di, d_model), 1.0,
                                           dtype),
    }


def _mlstm_qkv_gates(p: Params, x: jax.Array, cfg: XLSTMConfig,
                     conv_state: Optional[jax.Array] = None):
    B, T, D = x.shape
    di = cfg.expand * D
    hd = di // cfg.n_heads
    up = x @ p["up_proj"]
    xin, z = up[..., :di], up[..., di:]
    cx, new_conv = _causal_conv(xin, p["conv_w"], p["conv_b"],
                                prev=conv_state)
    cx = jax.nn.silu(cx.astype(jnp.float32)).astype(x.dtype)
    q = (cx @ p["wq"]).reshape(B, T, cfg.n_heads, hd)
    k = (cx @ p["wk"]).reshape(B, T, cfg.n_heads, hd) / jnp.sqrt(hd).astype(
        x.dtype)
    v = (xin @ p["wv"]).reshape(B, T, cfg.n_heads, hd)
    gates = (xin.astype(jnp.float32) @ p["w_gates"])      # [B,T,2H]
    ig = jnp.clip(gates[..., :cfg.n_heads] + p["b_igate"], -8.0, 8.0)
    fg = gates[..., cfg.n_heads:] + p["b_fgate"]
    log_decay = jax.nn.log_sigmoid(fg)
    k = k * jnp.exp(ig).astype(k.dtype)[..., None]
    return q, k, v, log_decay, z, new_conv


def apply_mlstm(p: Params, x: jax.Array, cfg: XLSTMConfig,
                use_kernel: bool = False, eps: float = 1e-6) -> jax.Array:
    B, T, D = x.shape
    di = cfg.expand * D
    q, k, v, log_decay, z, _ = _mlstm_qkv_gates(p, x, cfg)
    y, _ = chunked_gla(q, k, v, log_decay, chunk=cfg.chunk, normalize=True,
                       use_kernel=use_kernel)
    y = y.reshape(B, T, di)
    y = rms_norm(y, p["norm_w"], eps)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return y @ p["down_proj"]


def prefill_mlstm(p: Params, x: jax.Array, cfg: XLSTMConfig,
                  use_kernel: bool = False) -> Tuple[jax.Array, Params]:
    """Prefill: also return the recurrent cache for decode."""
    B, T, D = x.shape
    di = cfg.expand * D
    q, k, v, log_decay, z, new_conv = _mlstm_qkv_gates(p, x, cfg)
    y, (S, n) = chunked_gla(q, k, v, log_decay, chunk=cfg.chunk,
                            normalize=True, use_kernel=use_kernel)
    y = y.reshape(B, T, di)
    y = rms_norm(y, p["norm_w"])
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return y @ p["down_proj"], {"conv": new_conv, "S": S, "n": n}


def init_mlstm_cache(batch: int, d_model: int, cfg: XLSTMConfig, dtype
                     ) -> Params:
    di = cfg.expand * d_model
    hd = di // cfg.n_heads
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, di), dtype),
        "S": jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, cfg.n_heads, hd), jnp.float32),
    }


def decode_mlstm(p: Params, x: jax.Array, cache: Params, cfg: XLSTMConfig
                 ) -> Tuple[jax.Array, Params]:
    B, _, D = x.shape
    di = cfg.expand * D
    q, k, v, log_decay, z, new_conv = _mlstm_qkv_gates(
        p, x, cfg, conv_state=cache["conv"])
    y, (S, n) = gla_decode_step(q[:, 0], k[:, 0], v[:, 0], log_decay[:, 0],
                                (cache["S"], cache["n"]), normalize=True)
    y = y.reshape(B, 1, di)
    y = rms_norm(y, p["norm_w"])
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return y @ p["down_proj"], {"conv": new_conv, "S": S, "n": n}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def init_slstm(key: jax.Array, d_model: int, cfg: XLSTMConfig, dtype
               ) -> Params:
    hd = d_model // cfg.n_heads
    ks = jax.random.split(key, 3)
    return {
        "w_in": truncated_normal_init(ks[0], (d_model, 4 * d_model), 1.0,
                                      jnp.float32),
        "r": truncated_normal_init(ks[1], (cfg.n_heads, hd, 4 * hd), 1.0,
                                   jnp.float32),
        "b": jnp.concatenate([
            jnp.zeros((d_model,), jnp.float32),        # i
            jnp.full((d_model,), 3.0, jnp.float32),    # f
            jnp.zeros((2 * d_model,), jnp.float32),    # z, o
        ]),
        "norm_w": jnp.zeros((d_model,), dtype),
        "out_proj": truncated_normal_init(ks[2], (d_model, d_model), 1.0,
                                          dtype),
    }


def _slstm_cell(carry, gates_x, nh: int, hd: int, r: jax.Array):
    """One time step.  carry = (c, n, h, m) each [B, nh, hd] f32."""
    c, n, h, m = carry
    rec = jnp.einsum("bhd,hdg->bhg", h, r)              # [B, nh, 4hd]
    pre = gates_x + rec
    it, ft, zt, ot = jnp.split(pre, 4, axis=-1)
    m_new = jnp.maximum(ft + m, it)
    i = jnp.exp(it - m_new)
    f = jnp.exp(ft + m - m_new)
    c_new = f * c + i * jnp.tanh(zt)
    n_new = f * n + i
    h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, h_new, m_new), h_new


def _slstm_forward(p: Params, x: jax.Array, cfg: XLSTMConfig,
                   carry: Optional[Tuple] = None, eps: float = 1e-6):
    """Strictly sequential over T (lax.scan).  Returns (y, final_carry)."""
    B, T, D = x.shape
    nh = cfg.n_heads
    hd = D // nh
    gx = (x.astype(jnp.float32) @ p["w_in"] + p["b"])    # [B,T,4D]
    # regroup gate layout from [4*D] to per-head [nh, 4*hd]
    gx = gx.reshape(B, T, 4, nh, hd).transpose(0, 1, 3, 2, 4).reshape(
        B, T, nh, 4 * hd)
    if carry is None:
        zeros = jnp.zeros((B, nh, hd), jnp.float32)
        carry = (zeros, zeros, zeros, zeros)
    carry, hs = jax.lax.scan(
        lambda carry, g: _slstm_cell(carry, g, nh, hd, p["r"]),
        carry, gx.swapaxes(0, 1))
    y = hs.swapaxes(0, 1).reshape(B, T, D).astype(x.dtype)
    y = rms_norm(y, p["norm_w"], eps)
    return y @ p["out_proj"], carry


def apply_slstm(p: Params, x: jax.Array, cfg: XLSTMConfig,
                eps: float = 1e-6) -> jax.Array:
    y, _ = _slstm_forward(p, x, cfg, eps=eps)
    return y


def prefill_slstm(p: Params, x: jax.Array, cfg: XLSTMConfig
                  ) -> Tuple[jax.Array, Params]:
    y, (c, n, h, m) = _slstm_forward(p, x, cfg)
    return y, {"c": c, "n": n, "h": h, "m": m}


def init_slstm_cache(batch: int, d_model: int, cfg: XLSTMConfig) -> Params:
    hd = d_model // cfg.n_heads
    z = jnp.zeros((batch, cfg.n_heads, hd), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": z}


def decode_slstm(p: Params, x: jax.Array, cache: Params, cfg: XLSTMConfig
                 ) -> Tuple[jax.Array, Params]:
    B, _, D = x.shape
    nh = cfg.n_heads
    hd = D // nh
    gx = (x[:, 0].astype(jnp.float32) @ p["w_in"] + p["b"])
    gx = gx.reshape(B, 4, nh, hd).transpose(0, 2, 1, 3).reshape(
        B, nh, 4 * hd)
    carry = (cache["c"], cache["n"], cache["h"], cache["m"])
    (c, n, h, m), h_out = _slstm_cell(carry, gx, nh, hd, p["r"])
    y = h_out.reshape(B, 1, D).astype(x.dtype)
    y = rms_norm(y, p["norm_w"])
    return y @ p["out_proj"], {"c": c, "n": n, "h": h, "m": m}
