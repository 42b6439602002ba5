"""Jit'd public wrappers around the Pallas kernels.

* :func:`flash_attention` — model-layout GQA flash attention; its
  backward is two Pallas calls over the blocks the mask can keep
  (consumes the forward's LSE).
* :func:`gla_scan` — chunked gated linear recurrence; its backward is
  one Pallas call that walks the chunks in reverse (``normalize=False``),
  or, for the normalized form (xLSTM's mLSTM), the vjp of the chunked jnp
  reference (``models/lm/gla.chunked_gla``).  Calls with an initial
  state never reach the kernels.
* :func:`quantize_int8` / :func:`dequantize_int8` — unbiased int8
  compression for the tiered gradient sync.

The Pallas calls are named (``flash_attention_fwd``,
``flash_attention_bwd_dkv``, ``flash_attention_bwd_dq``, ``gla_scan_fwd``,
``gla_scan_bwd``, ``int8_quant``) and the backward passes run under the
named scopes ``flash_attention_bwd`` and ``gla_scan_bwd``, so a profiler
trace finds each kernel's work by name whatever implements it.

On non-TPU backends the kernels run in ``interpret=True`` mode (the
kernel body executes as traced JAX ops) — numerically identical, which
is what the oracle tests rely on.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import flash_attention as fa
from repro.kernels import gla_scan as gs
from repro.kernels import int8_quant as iq
from repro.kernels.tiling import pick_block
from repro.models.lm.gla import chunked_gla


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, window: int, block_q: int, block_k: int,
                scale: float, interpret: bool):
    @jax.custom_vjp
    def f(q, k, v):
        o, _ = fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret, scale=scale)
        return o

    def fwd(q, k, v):
        o, lse = fa.flash_attention_fwd(q, k, v, causal=causal,
                                        window=window, block_q=block_q,
                                        block_k=block_k,
                                        interpret=interpret, scale=scale)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        with obs.scope("flash_attention_bwd"):
            kept = fa.band(q.shape[1], k.shape[1], block_q, block_k, causal,
                           window)
            obs.count_blocks("flash_attention_bwd", int(kept.sum()),
                             kept.size)
            return fa.flash_attention_bwd(
                q, k, v, o, lse, do, causal=causal, window=window,
                block_q=block_q, block_k=block_k, interpret=interpret,
                scale=scale)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Model layout: q [B, T, H, hd]; k/v [B, S, KV, hd] -> [B, T, H, hd].
    Scores are ``q k^T * scale``, by default ``hd ** -0.5``."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    interp = _interpret() if interpret is None else interpret
    bq = pick_block(T, block_q)
    bk = pick_block(S, block_k)
    scale = 1.0 / (hd ** 0.5) if scale is None else float(scale)
    f = _make_flash(causal, int(window), bq, bk, scale, interp)
    qh = q.swapaxes(1, 2).reshape(B * H, T, hd)
    kh = k.swapaxes(1, 2).reshape(B * KV, S, hd)
    vh = v.swapaxes(1, 2).reshape(B * KV, S, hd)
    o = f(qh, kh, vh)
    return o.reshape(B, H, T, hd).swapaxes(1, 2)


# ---------------------------------------------------------------------------
# GLA scan
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_gla(chunk: int, normalize: bool, interpret: bool):
    @jax.custom_vjp
    def f(q, k, v, a):
        y, S, n = gs.gla_scan_fwd(q, k, v, a, chunk=chunk,
                                  normalize=normalize, interpret=interpret)
        return y, S, n

    def fwd(q, k, v, a):
        out = gs.gla_scan_fwd(q, k, v, a, chunk=chunk, normalize=normalize,
                              interpret=interpret)
        return out, (q, k, v, a)

    def bwd(res, cts):
        q, k, v, a = res
        nc = q.shape[1] // chunk
        with obs.scope("gla_scan_bwd"):
            if not normalize:
                obs.count_blocks("gla_scan_bwd", nc, nc)
                return gs.gla_scan_bwd(q, k, v, a, *cts, chunk=chunk,
                                       interpret=interpret)
            obs.count_blocks("gla_scan_bwd", 0, nc)

            def chunked(q, k, v, a):
                # Kernel layout [BH, T, d] as one-head model layout.
                y, (S, n) = chunked_gla(q[:, :, None], k[:, :, None],
                                        v[:, :, None], a[:, :, None],
                                        chunk=chunk, normalize=normalize)
                return y[:, :, 0], S[:, 0], n[:, 0]

            _, vjp = jax.vjp(chunked, q, k, v, a)
            return vjp(cts)

    f.defvjp(fwd, bwd)
    return f


def gla_scan(q: jax.Array, k: jax.Array, v: jax.Array,
             log_decay: jax.Array, *, chunk: int = 128,
             normalize: bool = False, initial_state=None,
             interpret: Optional[bool] = None):
    """Model layout: q/k [B, T, H, dk]; v [B, T, H, dv];
    log_decay [B, T, H].  Contract matches chunked_gla."""
    if initial_state is not None:
        # decode/chained-prefill path: stay on the jnp reference.
        return chunked_gla(q, k, v, log_decay, chunk=chunk,
                           normalize=normalize, initial_state=initial_state,
                           use_kernel=False)
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    interp = _interpret() if interpret is None else interpret
    f = _make_gla(min(chunk, T), normalize, interp)
    qh = q.swapaxes(1, 2).reshape(B * H, T, dk)
    kh = k.swapaxes(1, 2).reshape(B * H, T, dk)
    vh = v.swapaxes(1, 2).reshape(B * H, T, dv)
    ah = log_decay.astype(jnp.float32).swapaxes(1, 2).reshape(B * H, T)
    y, S, n = f(qh, kh, vh, ah)
    return (y.reshape(B, H, T, dv).swapaxes(1, 2),
            (S.reshape(B, H, dk, dv), n.reshape(B, H, dk)))


# ---------------------------------------------------------------------------
# Int8 compression
# ---------------------------------------------------------------------------

def quantize_int8(x: jax.Array, key: jax.Array, *,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """x: [M, N] rows, quantized with stochastic rounding.  Returns
    (q int8 [M, N], scale f32 [M]), which :func:`dequantize_int8`
    inverts."""
    interp = _interpret() if interpret is None else interpret
    noise = jax.random.uniform(key, x.shape, jnp.float32)
    return iq.quantize_int8(x, noise, interpret=interp)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return iq.dequantize_int8(q, scale)


def wire_qdq_int8(x: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Deterministic int8 wire round trip: per-*sample* rows (leading
    axis), absmax scaling, round-to-nearest (the stochastic-rounding
    noise pinned at 0.5, keeping compiled hybrid steps pure).  Returns
    the dequantized tensor in ``x``'s shape and dtype — exactly what the
    receiving worker reconstructs from ``elems + 4`` wire bytes/sample
    (see :mod:`repro.core.wire`)."""
    interp = _interpret() if interpret is None else interpret
    b = x.shape[0]
    flat = x.reshape(b, -1)
    noise = jnp.full(flat.shape, 0.5, jnp.float32)
    q, scale = iq.quantize_int8(flat, noise, interpret=interp)
    return iq.dequantize_int8(q, scale).reshape(x.shape).astype(x.dtype)
