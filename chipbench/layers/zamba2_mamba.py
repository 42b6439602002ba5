"""Zamba2's Mamba decoder layer (Zyphra/Zamba2-7B; the transformers
``Zamba2MambaDecoderLayer`` and ``Zamba2MambaMixer``).

The residual stream is ``[h ; e]`` ([T, 2D]): ``e``, the token
embedding, rides beside ``h`` to the hybrid layers that read it and
passes through here.  A [T, D] input is the embedding itself, and the
stream starts as ``[e ; e]``.

    h' = h + Mamba2(RMSNorm(h + t))      (t = 0 here; see zamba2_hybrid)

Mamba2: ``in_proj`` D -> [z | x | B | C | dt]; a depthwise causal conv
of width ``mamba_d_conv``, with bias, over xBC, then silu;
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h`` of
``n_mamba_heads`` (``mamba_headdim`` channels of x), B and C of group
``h // (heads / mamba_ngroups)``:

    y_t = sum_{s <= t} exp(sum_{r=s+1..t} A dt_r) (C_t . B_s) dt_s x_s + D x_t

written here in its quadratic masked form, in blocks of query rows
under ``jax.checkpoint`` (as ``attn.py`` does); then ``y * silu(z)``, an
RMSNorm over each group's ``d_inner / mamba_ngroups`` channels, and
``out_proj``.

The decay's exponent is a segment sum of the log-decays.  It is formed
from sums over short spans (a cumulative sum inside segments of
``SEG`` positions, and the segments' totals summed from the query's
segment back), never as the difference of two sums from position 0,
which at A up to 16 reach thousands, where float32 keeps only a few
digits of the difference.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.kernels.gla_scan_fwd import scan_flops
from chipbench.reference import _mm, rms_norm

Q_ROWS = 256        # query rows per SSD block
SEG = 16            # positions per segment of the decay's exponent
# Zamba2's init of A and dt: Mamba2's A_init_range, and the config's
# time_step_min / time_step_max / time_step_floor.
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4


def sizes(c: Dict) -> Dict:
    D = c["hidden_size"]
    di = c["mamba_expand"] * D
    G, N = c["mamba_ngroups"], c["mamba_d_state"]
    return {"D": D, "di": di, "G": G, "N": N, "P": c["mamba_headdim"],
            "nh": c["n_mamba_heads"], "K": c["mamba_d_conv"],
            "conv": di + 2 * G * N, "chunk": c["chunk_size"]}


def ssd(c: Dict) -> Dict:
    """The chunked scan the program runs for this layer (one per
    sequence): heads, key and value widths, chunk."""
    s = sizes(c)
    return {"heads": s["nh"], "dk": s["N"], "dv": s["P"],
            "chunk": s["chunk"]}


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))


def _dt_bias(key, shape):
    lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))            # softplus^-1(dt)


INIT = {"A_log": _a_log, "dt_bias": _dt_bias,
        "ones": lambda key, shape: jnp.ones(shape, jnp.float32)}


def shapes(c: Dict) -> Dict:
    s = sizes(c)
    D, di, nh, bf = s["D"], s["di"], s["nh"], c["dtype"]
    return {
        "pre": {"w": ((D,), bf, "zeros")},
        "m": {"in_proj": ((D, di + s["conv"] + nh), bf, "matrix"),
              "conv_w": ((s["K"], s["conv"]), bf, "matrix"),
              "conv_b": ((s["conv"],), bf, "zeros"),
              "A_log": ((nh,), "float32", "A_log"),
              "dt_bias": ((nh,), "float32", "dt_bias"),
              "D_skip": ((nh,), "float32", "ones"),
              "norm_w": ((di,), bf, "zeros"),
              "out_proj": ((di, D), bf, "matrix")}}


def _segment_sums(a):
    """``(loc [T, H], tot [T/SEG, T/SEG, H])``: ``loc`` the cumulative
    sum of ``a`` inside each segment, ``tot[i, j]`` the sum of segments
    ``j .. i - 1`` (0 for ``j >= i``), so that ``sum(a[s+1 .. t]) =
    loc[t] + tot[seg(t), seg(s)] - loc[s]`` for ``s <= t``."""
    T, H = a.shape
    n = T // SEG
    loc = jnp.cumsum(a.reshape(n, SEG, H), axis=1)
    seg = jnp.where(jnp.tril(jnp.ones((n, n), bool), -1)[..., None],
                    loc[None, :, -1, :], 0.0)                # [i, m, H]
    tot = jnp.cumsum(seg[:, ::-1], axis=1)[:, ::-1]
    return loc.reshape(T, H), tot


def scan(c: Dict, mode: str, x, B, C, dt, a):
    """y [T, nh, P] of the SSD recurrence: x [T, nh, P], B and C
    [T, G, N], dt and the log-decay ``a = A dt`` [T, nh]."""
    T, nh, P = x.shape
    G = B.shape[1]
    v = x * dt[..., None]
    loc, tot = _segment_sums(a)
    nb = T // Q_ROWS if T % Q_ROWS == 0 and T > Q_ROWS else 1
    bq = T // nb
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(i):
        rows = i * bq + jnp.arange(bq)
        cb = _mm(mode, "qgn,kgn->gqk",
                 jax.lax.dynamic_slice_in_dim(C, i * bq, bq), B)
        expo = (jax.lax.dynamic_slice_in_dim(loc, i * bq, bq)[:, None, :]
                + jnp.repeat(tot[rows // SEG], SEG, axis=1)
                - loc[None, :, :])                            # [q, k, h]
        keep = (rows[:, None] >= kpos[None, :])[..., None]
        decay = jnp.exp(jnp.where(keep, expo, -jnp.inf))
        s = jnp.repeat(cb, nh // G, axis=0) * decay.transpose(2, 0, 1)
        return _mm(mode, "hqk,khp->qhp", s, v)

    return jax.lax.map(block, jnp.arange(nb)).reshape(T, nh, P)


def mixer(c: Dict, mode: str, p, u):
    """Mamba2 on one normalized sequence ``u`` [T, D]."""
    s = sizes(c)
    T = u.shape[0]
    di, nh, G, N, K = s["di"], s["nh"], s["G"], s["N"], s["K"]
    zxbcdt = _mm(mode, "td,de->te", u, p["in_proj"])
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + s["conv"]],
                  zxbcdt[:, di + s["conv"]:])
    pad = jnp.concatenate([jnp.zeros((K - 1, s["conv"])), xbc])
    xbc = jax.nn.silu(sum(pad[i:i + T] * p["conv_w"][i] for i in range(K))
                      + p["conv_b"])
    x = xbc[:, :di].reshape(T, nh, s["P"])
    B = xbc[:, di:di + G * N].reshape(T, G, N)
    C = xbc[:, di + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = scan(c, mode, x, B, C, dt, -jnp.exp(p["A_log"]) * dt)
    y = (y + p["D_skip"][:, None] * x).reshape(T, di) * jax.nn.silu(z)
    y = rms_norm(y.reshape(T, G, di // G),
                 p["norm_w"].reshape(G, di // G),
                 c["rms_norm_eps"]).reshape(T, di)
    return _mm(mode, "te,ed->td", y, p["out_proj"])


def stream(c: Dict, h):
    """``h`` as ``[h ; e]`` ([T, 2D]); a [T, D] input is the embedding."""
    return jnp.concatenate([h, h], -1) if h.shape[-1] == \
        c["hidden_size"] else h


def layer(c: Dict, mode: str, p, h, t=0.0):
    """``[h + Mamba2(RMSNorm(h + t)) ; e]`` of the stream ``h``."""
    D = c["hidden_size"]
    x = h[:, :D]
    y = mixer(c, mode, p["m"], rms_norm(x + t, p["pre"]["w"],
                                        c["rms_norm_eps"]))
    return jnp.concatenate([x + y, h[:, D:]], -1)


def forward(c: Dict, mode: str, p, h):
    return layer(c, mode, p, stream(c, h))


def flops(c: Dict, T: int) -> float:
    s = sizes(c)
    D, di = s["D"], s["di"]
    proj = 2 * T * D * (di + s["conv"] + s["nh"]) + 2 * T * di * D
    return float(proj + s["nh"] * scan_flops(T, min(s["chunk"], T), s["N"],
                                             s["P"]))
