"""Zamba2's hybrid layer (the transformers ``Zamba2HybridLayer``): one
application of a shared transformer block, with the application's own
MLP adapter and linear, then its Mamba decoder layer.  On the stream
``[h ; e]`` ([T, 2D]; ``e`` the token embedding):

    u = RMSNorm_2D([h ; e])
    o = Attention(u) W_o                   (no residual)
    m = RMSNorm_D(o)
    [g | v] = m W_gate_up + (m A) B        (the rank-r adapter A, B)
    t = (gelu(g) * v) W_down L             (the linear L, D x D)
    h' = h + Mamba2(RMSNorm(h + t))        (``zamba2_mamba.layer``)

Attention: ``num_attention_heads`` heads of ``attention_head_dim``
from ``attention_hidden_size`` = 2D, RoPE over every dim of the head,
full causal, softmax scale ``(head_dim / 2) ** -1/2``, in blocks of
query rows under ``jax.checkpoint``.  GELU is the exact (erf) one.
Which shared block an application uses matters only where the program
ties copies; here each cut point holds its own, so the weights are this
layer's.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import pairs
from chipbench.layers import zamba2_mamba as mamba
from chipbench.reference import Q_BLOCK, _mm, rms_norm, rope

INIT = mamba.INIT
ssd = mamba.ssd


def sizes(c: Dict) -> Dict:
    D = c["hidden_size"]
    return {"D": D, "H": c["num_attention_heads"],
            "hd": c["attention_head_dim"], "A": c["attention_hidden_size"],
            "F": c["ffn_hidden_size"], "r": c["adapter_rank"]}


def attention(c: Dict) -> Dict:
    s = sizes(c)
    return {"H": s["H"], "KV": s["H"], "hd": s["hd"], "window": 0}


def shapes(c: Dict) -> Dict:
    s = sizes(c)
    D, A, HD, F, r, bf = (s["D"], s["A"], s["H"] * s["hd"], s["F"], s["r"],
                          c["dtype"])
    return {
        "adapter": {"down": ((D, r), bf, "matrix"),
                    "up": ((r, 2 * F), bf, "matrix")},
        "linear": ((D, D), bf, "matrix"),
        "shared": {
            "attn": {"wk": ((A, HD), bf, "matrix"),
                     "wo": ((HD, D), bf, "matrix"),
                     "wq": ((A, HD), bf, "matrix"),
                     "wv": ((A, HD), bf, "matrix")},
            "ln_in": {"w": ((A,), bf, "zeros")},
            "ln_mlp": {"w": ((D,), bf, "zeros")},
            "mlp": {"w_down": ((F, D), bf, "matrix"),
                    "w_gate_up": ((D, 2 * F), bf, "matrix")}},
        **mamba.shapes(c)}


def self_attention(c: Dict, mode: str, p, u):
    T = u.shape[0]
    s = sizes(c)
    H, hd = s["H"], s["hd"]
    q, k, v = (_mm(mode, "td,de->te", u, p[w]).reshape(T, H, hd)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    nb = T // Q_BLOCK if T % Q_BLOCK == 0 and T > Q_BLOCK else 1
    bq = T // nb

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq) / np.sqrt(hd / 2)
        sc = _mm(mode, "qhd,khd->hqk", qb, k)
        keep = i * bq + jnp.arange(bq)[:, None] >= jnp.arange(T)[None, :]
        sc = jnp.where(keep, sc, -jnp.inf)
        return _mm(mode, "hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(T, H * hd)
    return _mm(mode, "te,ed->td", o, p["wo"])


def shared_block(c: Dict, mode: str, p, adapter, h):
    """The shared transformer block with one application's adapter, on
    the stream ``h = [h ; e]``: its output ``t`` before the linear."""
    eps = c["rms_norm_eps"]
    o = self_attention(c, mode, p["attn"], rms_norm(h, p["ln_in"]["w"],
                                                    eps))
    m = rms_norm(o, p["ln_mlp"]["w"], eps)
    gu = _mm(mode, "td,df->tf", m, p["mlp"]["w_gate_up"]) + _mm(
        mode, "tr,rf->tf", _mm(mode, "td,dr->tr", m, adapter["down"]),
        adapter["up"])
    g, v = jnp.split(gu, 2, axis=-1)
    return _mm(mode, "tf,fd->td", jax.nn.gelu(g, approximate=False) * v,
               p["mlp"]["w_down"])


def forward(c: Dict, mode: str, p, h):
    h = mamba.stream(c, h)
    t = _mm(mode, "td,de->te",
            shared_block(c, mode, p["shared"], p["adapter"], h), p["linear"])
    return mamba.layer(c, mode, p, h, t)


def shared_flops(c: Dict, T: int) -> float:
    """Forward model FLOPs of the shared block with its adapter on one
    sequence (kept query-key pairs only)."""
    s = sizes(c)
    D, A, HD, F, r = s["D"], s["A"], s["H"] * s["hd"], s["F"], s["r"]
    return float(2 * T * (3 * A * HD + HD * D + 2 * D * F + r * (D + 2 * F)
                          + F * D) + 4 * pairs(T, T) * HD)


def flops(c: Dict, T: int) -> float:
    D = c["hidden_size"]
    return shared_flops(c, T) + 2.0 * T * D * D + mamba.flops(c, T)
