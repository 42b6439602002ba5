"""LM model zoo: one config dataclass, one builder, five families.

Families
--------
* ``dense``  — pre-norm decoder (GQA + SwiGLU), optional sliding/global
  attention pattern (gemma3), optional VLM/audio prefix embeddings (pixtral).
* ``moe``    — dense skeleton with the MLP replaced by a routed MoE
  (grok-1, qwen2-moe incl. shared experts).
* ``zamba``  — Mamba2 backbone with a single *shared* attention+MLP block
  applied every ``shared_attn_every`` layers (zamba2).
* ``xlstm``  — mLSTM blocks with an sLSTM block every ``slstm_every``
  (xlstm).
* ``encdec`` — whisper-style encoder-decoder with cross-attention; the audio
  conv frontend is a stub (precomputed frame embeddings are model inputs).

All stacks scan over layers (stacked params) so compiled HLO stays small for
the 512-device dry-runs.  Mixed attention patterns (gemma3's 5 local : 1
global) are realized as *grouped* scans so the window size stays a static
Python int in every sub-scan (a requirement for the Pallas flash kernel and
for cheap masks).  Every family exposes::

    init(key)                          -> params
    loss_fn(params, batch)             -> scalar  (train objective)
    prefill(params, batch, max_len)    -> (last_logits, cache)
    decode_step(params, tok, cache, pos) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import attention as attn
from repro.models.lm import moe as moe_mod
from repro.models.lm import ssm as ssm_mod
from repro.models.lm import xlstm as xlstm_mod
from repro.models.lm.common import (Params, apply_geglu, apply_gelu_mlp,
                                    apply_swiglu, chunked_softmax_xent,
                                    init_gelu_mlp, init_swiglu, layer_norm,
                                    rms_norm, shard_hint,
                                    sinusoidal_position_at,
                                    sinusoidal_positions,
                                    truncated_normal_init)
from repro.models.lm.moe import MoEConfig
from repro.models.lm.ssm import SSMConfig
from repro.models.lm.xlstm import XLSTMConfig


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    family: str                     # dense | moe | zamba | xlstm | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 1e4
    sliding_window: int = 0         # local attention width (0 = full)
    global_every: int = 0           # gemma3: every k-th layer is global
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    shared_attn_every: int = 0      # zamba
    # zamba LayerStack (``layerstack.py``): the published hybrid layers'
    # ids (default: every ``shared_attn_every``-th), how many shared
    # blocks they take in turn, the rank of each application's MLP
    # adapter (0: none), and the published layer the stack starts at.
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 1
    adapter_rank: int = 0
    first_layer: int = 0
    encoder_layers: int = 0
    n_frontend_tokens: int = 0      # stub prefix length (frames / patches)
    norm: str = "rms"               # rms | layer
    rms_norm_eps: float = 1e-6
    mlp: str = "swiglu"             # swiglu | geglu | gelu
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "none"      # none | dots
    loss_chunk: int = 512
    attn_block_q: int = 512         # blocked-attention q tile (0 = off)
    seq_parallel: bool = False      # Megatron-SP residual (T over model)
    use_flash: bool = False
    use_gla_kernel: bool = False
    sub_quadratic: bool = False     # True => long_500k decode is eligible

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def variant(self, **kw) -> "LMConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# norm / mlp dispatch
# ---------------------------------------------------------------------------

def _resid_hint(cfg: LMConfig, x: jax.Array) -> jax.Array:
    """Residual-stream sharding: batch over DP; with ``seq_parallel``
    also T over `model` (Megatron-SP) — shrinks the layer-scan's saved
    residual stack model_size-fold at the cost of per-layer attention
    reshards, so the launcher enables it only when the stack would
    otherwise blow the HBM budget (measured: grok-1 12.9 GB -> 0.8 GB,
    but qwen2.5's collective term grows 29% for a stack that already
    fits)."""
    return shard_hint(x, ("pod", "data"),
                      "model" if cfg.seq_parallel else None, None)


def _init_norm(cfg: LMConfig) -> Params:
    if cfg.norm == "layer":
        return {"w": jnp.ones((cfg.d_model,), cfg.dtype),
                "b": jnp.zeros((cfg.d_model,), cfg.dtype)}
    return {"w": jnp.zeros((cfg.d_model,), cfg.dtype)}


def _apply_norm(cfg: LMConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "layer":
        return layer_norm(x, p["w"], p["b"])
    return rms_norm(x, p["w"], cfg.rms_norm_eps)


def _init_mlp(key, cfg: LMConfig) -> Params:
    if cfg.mlp == "gelu":
        return init_gelu_mlp(key, cfg.d_model, cfg.d_ff, cfg.dtype)
    return init_swiglu(key, cfg.d_model, cfg.d_ff, cfg.dtype)


def _apply_mlp(cfg: LMConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.mlp == "gelu":
        return apply_gelu_mlp(p, x)
    if cfg.mlp == "geglu":
        return apply_geglu(p, x)
    return apply_swiglu(p, x)


# ---------------------------------------------------------------------------
# transformer block (dense / moe families; also zamba's shared block)
# ---------------------------------------------------------------------------

def _init_block(key, cfg: LMConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": _init_norm(cfg),
        "attn": attn.init_attention(k1, cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.hd, cfg.dtype,
                                    cfg.qkv_bias),
        "ln2": _init_norm(cfg),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe(k2, cfg.d_model, cfg.moe, cfg.dtype)
    else:
        p["mlp"] = _init_mlp(k3, cfg)
    return p


def _apply_block(cfg: LMConfig, p: Params, x: jax.Array, window: int,
                 positions: Optional[jax.Array] = None,
                 causal: bool = True) -> jax.Array:
    x = _resid_hint(cfg, x)
    h = _apply_norm(cfg, p["ln1"], x)
    h = attn.self_attention(
        p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd, causal=causal, rope_theta=cfg.rope_theta,
        window=window, positions=positions, use_flash=cfg.use_flash,
        block_q=cfg.attn_block_q)
    x = x + h
    h = _apply_norm(cfg, p["ln2"], x)
    if cfg.family == "moe":
        h = moe_mod.apply_moe(p["moe"], h, cfg.moe)
    else:
        h = _apply_mlp(cfg, p["mlp"], h)
    return x + h


def _prefill_block(cfg: LMConfig, p: Params, x: jax.Array, max_len: int,
                   window: int) -> Tuple[jax.Array, Params]:
    """Transformer block forward that also emits its (padded) KV cache."""
    B, T, _ = x.shape
    x = _resid_hint(cfg, x)
    h = _apply_norm(cfg, p["ln1"], x)
    q, k, v = attn._project_qkv(p["attn"], h, h, cfg.n_heads,
                                cfg.n_kv_heads, cfg.hd)
    q, k, v = attn._qkv_hints(q, k, v)
    pos = jnp.arange(T)
    if cfg.rope_theta > 0:
        q = attn.apply_rope(q, pos, cfg.rope_theta)
        k = attn.apply_rope(k, pos, cfg.rope_theta)
    if cfg.use_flash:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=True, window=window)
    else:
        o = attn.mha(q, k, v, causal=True, window=window,
                     block_q=cfg.attn_block_q)
    x = x + o.reshape(B, T, -1) @ p["attn"]["wo"]
    h = _apply_norm(cfg, p["ln2"], x)
    if cfg.family == "moe":
        h = moe_mod.apply_moe(p["moe"], h, cfg.moe)
    else:
        h = _apply_mlp(cfg, p["mlp"], h)
    pad = jnp.zeros((B, max_len - T) + k.shape[2:], cfg.dtype)
    cache = {"k": jnp.concatenate([k.astype(cfg.dtype), pad], axis=1),
             "v": jnp.concatenate([v.astype(cfg.dtype), pad], axis=1)}
    return x + h, cache


def _decode_block(cfg: LMConfig, p: Params, x: jax.Array, cache: Params,
                  pos: jax.Array, window: int) -> Tuple[jax.Array, Params]:
    h = _apply_norm(cfg, p["ln1"], x)
    h, ck, cv = attn.decode_self_attention(
        p["attn"], h, cache["k"], cache["v"], pos, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        rope_theta=cfg.rope_theta, window=window)
    x = x + h
    h = _apply_norm(cfg, p["ln2"], x)
    if cfg.family == "moe":
        h = moe_mod.apply_moe(p["moe"], h, cfg.moe)
    else:
        h = _apply_mlp(cfg, p["mlp"], h)
    return x + h, {"k": ck, "v": cv}


def _maybe_remat(cfg: LMConfig, fn: Callable) -> Callable:
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Model build — per family
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: LMConfig
    init: Callable[[jax.Array], Params]
    hidden_fn: Callable[[Params, Dict[str, jax.Array]], jax.Array]
    loss_fn: Callable[[Params, Dict[str, jax.Array]], jax.Array]
    prefill: Callable[..., Tuple[jax.Array, Params]]
    decode_step: Callable[..., Tuple[jax.Array, Params]]
    init_cache: Callable[..., Params]   # (batch, max_len, **kw) -> cache


def build_model(cfg: LMConfig) -> Model:
    if cfg.family in ("dense", "moe"):
        return _build_decoder(cfg)
    if cfg.family == "zamba":
        return _build_zamba(cfg)
    if cfg.family == "xlstm":
        return _build_xlstm(cfg)
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    raise ValueError(f"unknown family {cfg.family}")


# --- shared head/embedding helpers ----------------------------------------

def _init_head(key, cfg: LMConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "embed": truncated_normal_init(k1, (cfg.vocab, cfg.d_model), 1.0,
                                       cfg.dtype),
        "final_norm": _init_norm(cfg),
        "lm_head": truncated_normal_init(k2, (cfg.d_model, cfg.vocab), 1.0,
                                         cfg.dtype),
    }


def _embed_tokens(params: Params, tokens: jax.Array) -> jax.Array:
    # Gather from a table split over vocab only.  A table split over both
    # dims, gathered inside a shard_map that leaves those axes Auto (the
    # hier_sync step), makes XLA's SPMD partitioner abort on jax 0.9.0
    # (spmd_partitioner_util.cc "partition_group_list" CHECK).
    return jnp.take(shard_hint(params["embed"], "model", None), tokens,
                    axis=0)


def _prefix_embeds(params: Params, batch: Dict[str, jax.Array],
                   cfg: LMConfig) -> jax.Array:
    """token embeddings, with optional frontend-stub prefix concatenated."""
    x = _embed_tokens(params, batch["tokens"])
    if "embeds" in batch:
        x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
    return x


def _loss_from_hidden(cfg: LMConfig, params: Params, hidden: jax.Array,
                      batch: Dict[str, jax.Array]) -> jax.Array:
    hidden = _apply_norm(cfg, params["final_norm"], hidden)
    if "embeds" in batch:  # prefix positions carry no LM loss
        hidden = hidden[:, batch["embeds"].shape[1]:]
    mask = batch.get("mask")
    return chunked_softmax_xent(hidden, params["lm_head"],
                                batch["targets"], mask,
                                chunk=cfg.loss_chunk)


def _last_logits(cfg: LMConfig, params: Params, x: jax.Array) -> jax.Array:
    x = _apply_norm(cfg, params["final_norm"], x[:, -1:])
    return (x @ params["lm_head"]).astype(jnp.float32)[:, 0]


def _group_layout(cfg: LMConfig) -> Tuple[int, int, int]:
    """(n_groups, group_size, n_rest) of the local/global layer pattern.

    group_size == 0 means "uniform window" (single scan, no grouping).
    """
    if cfg.sliding_window and cfg.global_every:
        g = cfg.global_every
        return cfg.n_layers // g, g, cfg.n_layers % g
    return 0, 0, cfg.n_layers


def _split_groups(stacked: Params, n_groups: int, g: int
                  ) -> Tuple[Params, Params, Params]:
    """Split [L, ...] stacked params into (local [ng, g-1, ...],
    global [ng, ...], rest [n_rest, ...])."""
    def take_local(a):
        return a[:n_groups * g].reshape((n_groups, g) + a.shape[1:])[:, :-1]

    def take_global(a):
        return a[:n_groups * g].reshape((n_groups, g) + a.shape[1:])[:, -1]

    local = jax.tree.map(take_local, stacked)
    glob = jax.tree.map(take_global, stacked)
    rest = jax.tree.map(lambda a: a[n_groups * g:], stacked)
    return local, glob, rest


# --- dense / moe decoder ----------------------------------------------------

def _build_decoder(cfg: LMConfig) -> Model:
    ng, g, n_rest = _group_layout(cfg)
    sw = cfg.sliding_window

    def init(key: jax.Array) -> Params:
        kh, kl = jax.random.split(key)
        layer_keys = jax.random.split(kl, cfg.n_layers)
        layers = jax.vmap(lambda k: _init_block(k, cfg))(layer_keys)
        p = _init_head(kh, cfg)
        p["layers"] = layers
        return p

    def _stack_apply(x: jax.Array, stacked: Params, window: int,
                     positions=None) -> jax.Array:
        def body(x, lp):
            return _apply_block(cfg, lp, x, window, positions), None
        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, stacked)
        return x

    def hidden_fn(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        x = _prefix_embeds(params, batch, cfg)
        if ng == 0:  # uniform window
            return _stack_apply(x, params["layers"], sw)
        local, glob, rest = _split_groups(params["layers"], ng, g)

        def group_body(x, gp):
            lp, gp_glob = gp
            x = _stack_apply(x, lp, sw)
            x = _maybe_remat(cfg, lambda x, p: _apply_block(
                cfg, p, x, 0))(x, gp_glob)
            return x, None

        x, _ = jax.lax.scan(group_body, x, (local, glob))
        if n_rest:
            x = _stack_apply(x, rest, sw)
        return x

    def loss_fn(params, batch):
        return _loss_from_hidden(cfg, params, hidden_fn(params, batch),
                                 batch)

    def init_cache(batch: int, max_len: int) -> Params:
        return {
            "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                            cfg.hd), cfg.dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                            cfg.hd), cfg.dtype),
        }

    def _stack_prefill(x, stacked, max_len, window):
        def body(x, lp):
            return _prefill_block(cfg, lp, x, max_len, window)
        return jax.lax.scan(_maybe_remat(cfg, body), x, stacked)

    def prefill(params: Params, batch: Dict[str, jax.Array], max_len: int
                ) -> Tuple[jax.Array, Params]:
        """Run the full prompt, return (last-position logits, filled cache)."""
        x = _prefix_embeds(params, batch, cfg)
        if ng == 0:
            x, cache = _stack_prefill(x, params["layers"], max_len, sw)
            return _last_logits(cfg, params, x), cache
        local, glob, rest = _split_groups(params["layers"], ng, g)

        def group_body(x, gp):
            lp, gp_glob = gp
            x, c_local = _stack_prefill(x, lp, max_len, sw)
            x, c_glob = _prefill_block(cfg, gp_glob, x, max_len, 0)
            return x, (c_local, c_glob)

        x, (c_local, c_glob) = jax.lax.scan(group_body, x, (local, glob))
        caches = [(c_local, c_glob)]
        if n_rest:
            x, c_rest = _stack_prefill(x, rest, max_len, sw)
            caches.append(c_rest)
        cache = _merge_group_caches(caches, ng, g, n_rest)
        return _last_logits(cfg, params, x), cache

    def _merge_group_caches(caches, ng, g, n_rest):
        (c_local, c_glob) = caches[0]
        def merge(loc, glo):
            # loc: [ng, g-1, B, ...]; glo: [ng, B, ...] -> [ng*g, B, ...]
            return jnp.concatenate([loc, glo[:, None]], axis=1).reshape(
                (ng * g,) + loc.shape[2:])
        full = jax.tree.map(merge, c_local, c_glob)
        if n_rest:
            full = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                                full, caches[1])
        return full

    def decode_step(params: Params, tok: jax.Array, cache: Params,
                    pos: jax.Array) -> Tuple[jax.Array, Params]:
        x = _embed_tokens(params, tok)          # [B, 1, D]
        if ng == 0:
            def body(x, xs):
                lp, lc = xs
                return _decode_block(cfg, lp, x, lc, pos, sw)
            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
            return _last_logits(cfg, params, x), new_cache

        local, glob, rest = _split_groups(params["layers"], ng, g)
        cl, cg, cr = _split_groups(cache, ng, g)

        def group_body(x, xs):
            lp, gp_glob, lc, gc = xs

            def body(x, ys):
                p, c = ys
                return _decode_block(cfg, p, x, c, pos, sw)
            x, nc_local = jax.lax.scan(body, x, (lp, lc))
            x, nc_glob = _decode_block(cfg, gp_glob, x, gc, pos, 0)
            return x, (nc_local, nc_glob)

        x, (ncl, ncg) = jax.lax.scan(group_body, x, (local, glob, cl, cg))
        caches = [(ncl, ncg)]
        if n_rest:
            def body(x, ys):
                p, c = ys
                return _decode_block(cfg, p, x, c, pos, sw)
            x, ncr = jax.lax.scan(body, x, (rest, cr))
            caches.append(ncr)
        new_cache = _merge_group_caches(caches, ng, g, n_rest)
        return _last_logits(cfg, params, x), new_cache

    return Model(cfg, init, hidden_fn, loss_fn, prefill, decode_step,
                 init_cache)


# --- zamba: mamba2 backbone + shared attention block ------------------------

def _build_zamba(cfg: LMConfig) -> Model:
    assert cfg.ssm is not None and cfg.shared_attn_every > 0
    g = cfg.shared_attn_every
    ng = cfg.n_layers // g                      # groups ending in shared blk
    n_rest = cfg.n_layers - ng * g

    def init(key: jax.Array) -> Params:
        kh, km, ks = jax.random.split(key, 3)
        layer_keys = jax.random.split(km, cfg.n_layers)

        def init_layer(k):
            return {"pre": _init_norm(cfg),
                    "m": ssm_mod.init_mamba2(k, cfg.d_model, cfg.ssm,
                                             cfg.dtype)}
        p = _init_head(kh, cfg)
        p["mamba"] = jax.vmap(init_layer)(layer_keys)
        p["shared"] = _init_block(ks, cfg)
        return p

    def _grouped(stacked):
        first = jax.tree.map(
            lambda a: a[:ng * g].reshape((ng, g) + a.shape[1:]), stacked)
        rest = jax.tree.map(lambda a: a[ng * g:], stacked)
        return first, rest

    def _mamba_body(x, lp):
        x = _resid_hint(cfg, x)
        h = _apply_norm(cfg, lp["pre"], x)
        return x + ssm_mod.apply_mamba2(lp["m"], h, cfg.ssm,
                                        use_kernel=cfg.use_gla_kernel,
                                        eps=cfg.rms_norm_eps), None

    def _mamba_stack(x, stacked):
        x, _ = jax.lax.scan(_maybe_remat(cfg, _mamba_body), x, stacked)
        return x

    def hidden_fn(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        x = _embed_tokens(params, batch["tokens"])
        first, rest = _grouped(params["mamba"])
        shared = params["shared"]

        def group_body(x, gp):
            x = _mamba_stack(x, gp)
            x = _maybe_remat(cfg, lambda x, p: _apply_block(
                cfg, p, x, cfg.sliding_window))(x, shared)
            return x, None

        x, _ = jax.lax.scan(group_body, x, first)
        if n_rest:
            x = _mamba_stack(x, rest)
        return x

    def loss_fn(params, batch):
        return _loss_from_hidden(cfg, params, hidden_fn(params, batch),
                                 batch)

    def init_cache(batch: int, max_len: int) -> Params:
        m = ssm_mod.init_mamba2_cache(batch, cfg.d_model, cfg.ssm, cfg.dtype)
        return {
            "mamba": jax.tree.map(
                lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), m),
            "attn": {
                "k": jnp.zeros((ng, batch, max_len, cfg.n_kv_heads, cfg.hd),
                               cfg.dtype),
                "v": jnp.zeros((ng, batch, max_len, cfg.n_kv_heads, cfg.hd),
                               cfg.dtype),
            },
        }

    def prefill(params: Params, batch: Dict[str, jax.Array], max_len: int
                ) -> Tuple[jax.Array, Params]:
        x = _embed_tokens(params, batch["tokens"])
        first, rest = _grouped(params["mamba"])
        shared = params["shared"]

        def m_body(x, lp):
            h = _apply_norm(cfg, lp["pre"], x)
            y, c = ssm_mod.prefill_mamba2(lp["m"], h, cfg.ssm,
                                          use_kernel=cfg.use_gla_kernel,
                                          eps=cfg.rms_norm_eps)
            return x + y, c

        def group_body(x, gp):
            x, mc = jax.lax.scan(_maybe_remat(cfg, m_body), x, gp)
            x, ac = _prefill_block(cfg, shared, x, max_len,
                                   cfg.sliding_window)
            return x, (mc, ac)

        x, (mc_first, ac) = jax.lax.scan(group_body, x, first)
        # mc_first: [ng, g, ...] -> flatten to [ng*g, ...]
        mcache = jax.tree.map(
            lambda a: a.reshape((ng * g,) + a.shape[2:]), mc_first)
        if n_rest:
            x, mc_rest = jax.lax.scan(_maybe_remat(cfg, m_body), x, rest)
            mcache = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), mcache, mc_rest)
        return (_last_logits(cfg, params, x),
                {"mamba": mcache, "attn": ac})

    def decode_step(params: Params, tok: jax.Array, cache: Params,
                    pos: jax.Array) -> Tuple[jax.Array, Params]:
        x = _embed_tokens(params, tok)
        first, rest = _grouped(params["mamba"])
        mc_first, mc_rest = _grouped(cache["mamba"])
        shared = params["shared"]

        def m_body(x, xs):
            lp, lc = xs
            h = _apply_norm(cfg, lp["pre"], x)
            y, nc = ssm_mod.decode_mamba2(lp["m"], h, lc, cfg.ssm,
                                          cfg.rms_norm_eps)
            return x + y, nc

        def group_body(x, xs):
            gp, mc, ac = xs
            x, nmc = jax.lax.scan(m_body, x, (gp, mc))
            x, nac = _decode_block(cfg, shared, x, ac, pos,
                                   cfg.sliding_window)
            return x, (nmc, nac)

        x, (nmc_first, nac) = jax.lax.scan(
            group_body, x, (first, mc_first, cache["attn"]))
        mcache = jax.tree.map(
            lambda a: a.reshape((ng * g,) + a.shape[2:]), nmc_first)
        if n_rest:
            x, nmc_rest = jax.lax.scan(m_body, x, (rest, mc_rest))
            mcache = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), mcache, nmc_rest)
        return (_last_logits(cfg, params, x),
                {"mamba": mcache, "attn": nac})

    return Model(cfg, init, hidden_fn, loss_fn, prefill, decode_step,
                 init_cache)


# --- xlstm -------------------------------------------------------------------

def _build_xlstm(cfg: LMConfig) -> Model:
    assert cfg.xlstm is not None
    xc = cfg.xlstm
    g = xc.slstm_every
    if g > 0:
        assert cfg.n_layers % g == 0, "n_layers must divide slstm_every"
        ng = cfg.n_layers // g      # groups of (g-1) mLSTM + 1 sLSTM
        n_m_per_group = g - 1
    else:
        ng, n_m_per_group = 0, 0

    def init(key: jax.Array) -> Params:
        kh, km, ks = jax.random.split(key, 3)

        def init_m(k):
            return {"pre": _init_norm(cfg),
                    "m": xlstm_mod.init_mlstm(k, cfg.d_model, xc, cfg.dtype)}

        def init_s(k):
            return {"pre": _init_norm(cfg),
                    "s": xlstm_mod.init_slstm(k, cfg.d_model, xc, cfg.dtype)}

        p = _init_head(kh, cfg)
        if ng:
            mkeys = jax.random.split(km, ng * n_m_per_group)
            p["mlstm"] = jax.tree.map(
                lambda a: a.reshape((ng, n_m_per_group) + a.shape[1:]),
                jax.vmap(init_m)(mkeys))
            p["slstm"] = jax.vmap(init_s)(jax.random.split(ks, ng))
        else:
            p["mlstm"] = jax.vmap(init_m)(
                jax.random.split(km, cfg.n_layers))
        return p

    def _m_body(x, lp):
        x = _resid_hint(cfg, x)
        h = _apply_norm(cfg, lp["pre"], x)
        return x + xlstm_mod.apply_mlstm(lp["m"], h, xc,
                                         use_kernel=cfg.use_gla_kernel), None

    def _s_apply(x, lp):
        x = _resid_hint(cfg, x)
        h = _apply_norm(cfg, lp["pre"], x)
        return x + xlstm_mod.apply_slstm(lp["s"], h, xc)

    def hidden_fn(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        x = _embed_tokens(params, batch["tokens"])
        if not ng:
            x, _ = jax.lax.scan(_maybe_remat(cfg, _m_body), x,
                                params["mlstm"])
            return x

        def group_body(x, gp):
            mp, sp = gp
            x, _ = jax.lax.scan(_maybe_remat(cfg, _m_body), x, mp)
            x = _maybe_remat(cfg, _s_apply)(x, sp)
            return x, None

        x, _ = jax.lax.scan(group_body, x, (params["mlstm"],
                                            params["slstm"]))
        return x

    def loss_fn(params, batch):
        return _loss_from_hidden(cfg, params, hidden_fn(params, batch),
                                 batch)

    def init_cache(batch: int, max_len: int = 0) -> Params:
        mc = xlstm_mod.init_mlstm_cache(batch, cfg.d_model, xc, cfg.dtype)
        n_m = ng * n_m_per_group if ng else cfg.n_layers
        cache = {"mlstm": jax.tree.map(
            lambda a: jnp.zeros((n_m,) + a.shape, a.dtype), mc)}
        if ng:
            sc = xlstm_mod.init_slstm_cache(batch, cfg.d_model, xc)
            cache["slstm"] = jax.tree.map(
                lambda a: jnp.zeros((ng,) + a.shape, a.dtype), sc)
        return cache

    def _regroup(tree):     # [ng*m, ...] <- [ng, m, ...]
        return jax.tree.map(
            lambda a: a.reshape((ng * n_m_per_group,) + a.shape[2:]), tree)

    def prefill(params: Params, batch: Dict[str, jax.Array], max_len: int
                ) -> Tuple[jax.Array, Params]:
        x = _embed_tokens(params, batch["tokens"])

        def m_body(x, lp):
            h = _apply_norm(cfg, lp["pre"], x)
            y, c = xlstm_mod.prefill_mlstm(lp["m"], h, xc,
                                           use_kernel=cfg.use_gla_kernel)
            return x + y, c

        if not ng:
            x, mc = jax.lax.scan(_maybe_remat(cfg, m_body), x,
                                 params["mlstm"])
            return _last_logits(cfg, params, x), {"mlstm": mc}

        def group_body(x, gp):
            mp, sp = gp
            x, mc = jax.lax.scan(_maybe_remat(cfg, m_body), x, mp)
            h = _apply_norm(cfg, sp["pre"], x)
            y, sc = xlstm_mod.prefill_slstm(sp["s"], h, xc)
            return x + y, (mc, sc)

        x, (mc, sc) = jax.lax.scan(group_body, x,
                                   (params["mlstm"], params["slstm"]))
        return (_last_logits(cfg, params, x),
                {"mlstm": _regroup(mc), "slstm": sc})

    def decode_step(params: Params, tok: jax.Array, cache: Params,
                    pos: jax.Array) -> Tuple[jax.Array, Params]:
        x = _embed_tokens(params, tok)

        def m_body(x, xs):
            lp, lc = xs
            h = _apply_norm(cfg, lp["pre"], x)
            y, nc = xlstm_mod.decode_mlstm(lp["m"], h, lc, xc)
            return x + y, nc

        if not ng:
            x, nmc = jax.lax.scan(m_body, x,
                                  (params["mlstm"], cache["mlstm"]))
            return _last_logits(cfg, params, x), {"mlstm": nmc}

        mc_g = jax.tree.map(
            lambda a: a.reshape((ng, n_m_per_group) + a.shape[1:]),
            cache["mlstm"])

        def group_body(x, xs):
            mp, sp, mc, sc = xs
            x, nmc = jax.lax.scan(m_body, x, (mp, mc))
            h = _apply_norm(cfg, sp["pre"], x)
            y, nsc = xlstm_mod.decode_slstm(sp["s"], h, sc, xc)
            return x + y, (nmc, nsc)

        x, (nmc, nsc) = jax.lax.scan(
            group_body, x, (params["mlstm"], params["slstm"], mc_g,
                            cache["slstm"]))
        return (_last_logits(cfg, params, x),
                {"mlstm": _regroup(nmc), "slstm": nsc})

    return Model(cfg, init, hidden_fn, loss_fn, prefill, decode_step,
                 init_cache)


# --- encdec (whisper) --------------------------------------------------------

def _build_encdec(cfg: LMConfig) -> Model:
    assert cfg.encoder_layers > 0

    def _init_dec_block(key) -> Params:
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": _init_norm(cfg),
            "attn": attn.init_attention(k1, cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.hd, cfg.dtype),
            "lnx": _init_norm(cfg),
            "xattn": attn.init_attention(k2, cfg.d_model, cfg.n_heads,
                                         cfg.n_kv_heads, cfg.hd, cfg.dtype),
            "ln2": _init_norm(cfg),
            "mlp": _init_mlp(k3, cfg),
        }

    def init(key: jax.Array) -> Params:
        kh, ke, kd = jax.random.split(key, 3)
        p = _init_head(kh, cfg)
        p["enc_layers"] = jax.vmap(lambda k: _init_block(k, cfg))(
            jax.random.split(ke, cfg.encoder_layers))
        p["enc_norm"] = _init_norm(cfg)
        p["dec_layers"] = jax.vmap(_init_dec_block)(
            jax.random.split(kd, cfg.n_layers))
        return p

    def encode(params: Params, frames: jax.Array) -> jax.Array:
        """frames: [B, T_enc, D] precomputed embeddings (conv-frontend stub)."""
        T = frames.shape[1]
        x = frames.astype(cfg.dtype) + sinusoidal_positions(
            T, cfg.d_model).astype(cfg.dtype)[None]

        def body(x, lp):
            return _apply_block(cfg, lp, x, 0, causal=False), None

        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x,
                            params["enc_layers"])
        return _apply_norm(cfg, params["enc_norm"], x)

    def _dec_block(p: Params, x: jax.Array, enc_out: jax.Array) -> jax.Array:
        x = _resid_hint(cfg, x)
        h = _apply_norm(cfg, p["ln1"], x)
        h = attn.self_attention(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd, causal=True, rope_theta=cfg.rope_theta,
            use_flash=cfg.use_flash, block_q=cfg.attn_block_q)
        x = x + h
        h = _apply_norm(cfg, p["lnx"], x)
        h = attn.cross_attention(p["xattn"], h, enc_out,
                                 n_heads=cfg.n_heads,
                                 n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                                 block_q=cfg.attn_block_q)
        x = x + h
        h = _apply_norm(cfg, p["ln2"], x)
        return x + _apply_mlp(cfg, p["mlp"], h)

    def hidden_fn(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        enc_out = encode(params, batch["frames"])
        x = _embed_tokens(params, batch["tokens"])
        T = x.shape[1]
        x = x + sinusoidal_positions(T, cfg.d_model).astype(x.dtype)[None]

        def body(x, lp):
            return _dec_block(lp, x, enc_out), None

        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x,
                            params["dec_layers"])
        return x

    def loss_fn(params, batch):
        return _loss_from_hidden(cfg, params, hidden_fn(params, batch),
                                 batch)

    def init_cache(batch: int, max_len: int, enc_len: int = 0) -> Params:
        c = {
            "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                            cfg.hd), cfg.dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                            cfg.hd), cfg.dtype),
        }
        if enc_len:
            c["xk"] = jnp.zeros((cfg.n_layers, batch, enc_len,
                                 cfg.n_kv_heads, cfg.hd), cfg.dtype)
            c["xv"] = jnp.zeros_like(c["xk"])
        return c

    def prefill(params: Params, batch: Dict[str, jax.Array], max_len: int
                ) -> Tuple[jax.Array, Params]:
        enc_out = encode(params, batch["frames"])
        x = _embed_tokens(params, batch["tokens"])
        B, T = x.shape[:2]
        x = x + sinusoidal_positions(T, cfg.d_model).astype(x.dtype)[None]

        def body(x, lp):
            h = _apply_norm(cfg, lp["ln1"], x)
            q, k, v = attn._project_qkv(lp["attn"], h, h, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.hd)
            if cfg.rope_theta > 0:
                pos = jnp.arange(T)
                q = attn.apply_rope(q, pos, cfg.rope_theta)
                k = attn.apply_rope(k, pos, cfg.rope_theta)
            o = attn.mha(q, k, v, causal=True, block_q=cfg.attn_block_q)
            x = x + o.reshape(B, T, -1) @ lp["attn"]["wo"]
            h = _apply_norm(cfg, lp["lnx"], x)
            h = attn.cross_attention(lp["xattn"], h, enc_out,
                                     n_heads=cfg.n_heads,
                                     n_kv_heads=cfg.n_kv_heads,
                                     head_dim=cfg.hd,
                                     block_q=cfg.attn_block_q)
            x = x + h
            h = _apply_norm(cfg, lp["ln2"], x)
            x = x + _apply_mlp(cfg, lp["mlp"], h)
            # cross-attention K/V are static per request: cache them.
            _, xk, xv = attn._project_qkv(lp["xattn"], h, enc_out,
                                          cfg.n_heads, cfg.n_kv_heads,
                                          cfg.hd)
            pad = jnp.zeros((B, max_len - T) + k.shape[2:], cfg.dtype)
            return x, {"k": jnp.concatenate([k.astype(cfg.dtype), pad], 1),
                       "v": jnp.concatenate([v.astype(cfg.dtype), pad], 1),
                       "xk": xk.astype(cfg.dtype),
                       "xv": xv.astype(cfg.dtype)}

        x, cache = jax.lax.scan(_maybe_remat(cfg, body), x,
                                params["dec_layers"])
        return _last_logits(cfg, params, x), cache

    def decode_step(params: Params, tok: jax.Array, cache: Params,
                    pos: jax.Array) -> Tuple[jax.Array, Params]:
        x = _embed_tokens(params, tok)          # [B, 1, D]
        B = x.shape[0]
        x = x + sinusoidal_position_at(pos, cfg.d_model).astype(x.dtype)[
            None, None]

        def body(x, xs):
            lp, lc = xs
            h = _apply_norm(cfg, lp["ln1"], x)
            h, ck, cv = attn.decode_self_attention(
                lp["attn"], h, lc["k"], lc["v"], pos, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                rope_theta=cfg.rope_theta)
            x = x + h
            h = _apply_norm(cfg, lp["lnx"], x)
            q = (h @ lp["xattn"]["wq"]).reshape(B, 1, cfg.n_heads, cfg.hd)
            o = attn.mha(q, lc["xk"], lc["xv"], causal=False)
            x = x + o.reshape(B, 1, -1) @ lp["xattn"]["wo"]
            h = _apply_norm(cfg, lp["ln2"], x)
            x = x + _apply_mlp(cfg, lp["mlp"], h)
            return x, {"k": ck, "v": cv, "xk": lc["xk"], "xv": lc["xv"]}

        x, new_cache = jax.lax.scan(body, x, (params["dec_layers"], cache))
        return _last_logits(cfg, params, x), new_cache

    return Model(cfg, init, hidden_fn, loss_fn, prefill, decode_step,
                 init_cache)


# ---------------------------------------------------------------------------
# Parameter accounting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def param_count(params: Params) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))


def active_param_count(cfg: LMConfig, params: Params) -> int:
    """Parameters touched per token (MoE: top_k of n_experts)."""
    total = param_count(params)
    if cfg.family != "moe" or cfg.moe is None:
        return total
    expert_leaves = 0
    layers = params["layers"]
    for name in ("w_gate", "w_up", "w_down"):
        expert_leaves += int(np.prod(layers["moe"][name].shape))
    active = expert_leaves * cfg.moe.top_k / cfg.moe.n_experts
    return int(total - expert_leaves + active)
