"""Dense decoder block (Phi-3): pre-norm grouped-query self-attention
with RoPE, causal under the configuration's sliding window, then a
pre-norm SwiGLU MLP, each added to the residual stream."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import dims, pairs
from chipbench.reference import Q_BLOCK, _mm, rms_norm, rope


def attention(c: Dict) -> Dict:
    d = dims(c)
    return {k: d[k] for k in ("H", "KV", "hd", "window")}


def shapes(c: Dict) -> Dict:
    D, bf = c["hidden_size"], c["dtype"]
    d = dims(c)
    H, KV, hd, F = d["H"], d["KV"], d["hd"], d["F"]
    return {
        "ln1": {"w": ((D,), bf, "zeros")},
        "attn": {"wq": ((D, H * hd), bf, "matrix"),
                 "wk": ((D, KV * hd), bf, "matrix"),
                 "wv": ((D, KV * hd), bf, "matrix"),
                 "wo": ((H * hd, D), bf, "matrix")},
        "ln2": {"w": ((D,), bf, "zeros")},
        "mlp": {"w_gate": ((D, F), bf, "matrix"),
                "w_up": ((D, F), bf, "matrix"),
                "w_down": ((F, D), bf, "matrix")}}


def self_attention(c: Dict, mode: str, p, h):
    T = h.shape[0]
    d = dims(c)
    H, KV, hd, window = d["H"], d["KV"], d["hd"], d["window"]
    rep = H // KV
    q = rope(_mm(mode, "td,de->te", h, p["wq"]).reshape(T, H, hd),
             c["rope_theta"])
    k = rope(_mm(mode, "td,de->te", h, p["wk"]).reshape(T, KV, hd),
             c["rope_theta"])
    v = _mm(mode, "td,de->te", h, p["wv"]).reshape(T, KV, hd)
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = T // Q_BLOCK if T % Q_BLOCK == 0 and T > Q_BLOCK else 1
    bq = T // nb

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq) / np.sqrt(hd)
        s = _mm(mode, "qhd,khd->hqk", qb, k)
        qpos = i * bq + jnp.arange(bq)[:, None]
        kpos = jnp.arange(T)[None, :]
        keep = qpos >= kpos
        if window:
            keep &= kpos > qpos - window
        s = jnp.where(keep, s, -jnp.inf)
        return _mm(mode, "hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(T, H * hd)
    return _mm(mode, "te,ed->td", o, p["wo"])


def mlp(c: Dict, mode: str, p, h):
    """Gated MLP: silu(h W_gate) * (h W_up) W_down."""
    if c["hidden_act"] != "silu":
        raise ValueError(f"unknown hidden_act {c['hidden_act']!r}")
    g = jax.nn.silu(_mm(mode, "td,df->tf", h, p["w_gate"]))
    return _mm(mode, "tf,fd->td", g * _mm(mode, "td,df->tf", h,
                                          p["w_up"]), p["w_down"])


def forward(c: Dict, mode: str, p, h):
    eps = c["rms_norm_eps"]
    h = h + self_attention(c, mode, p["attn"],
                           rms_norm(h, p["ln1"]["w"], eps))
    return h + mlp(c, mode, p["mlp"], rms_norm(h, p["ln2"]["w"], eps))


def flops(c: Dict, T: int) -> float:
    d = dims(c)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    proj = 2 * T * D * (H * hd) * 2 + 2 * T * D * (KV * hd) * 2
    scores = 4 * pairs(T, T, d["window"]) * H * hd     # QK^T and AV
    return float(proj + scores + 3 * 2 * T * D * F)
