"""LayerStack adapter for the LM model zoo (DESIGN.md §8).

Exposes the transformer / GLA / MoE / xLSTM block stacks of
:mod:`repro.models.lm.model` to the HierTrain core — profiler, Algorithm-1
scheduler, hybrid execution engine, DES and train loops — as an ordered
chain of cut-points:

    [embed]  [block_1 ... block_K]  [head]

Cut-point granularity
---------------------
* ``embed`` pins naturally to the *stream start* (token ids are tiny —
  8 bytes/token sample wire cost — but the embedding table is huge, so a
  cut at 1 ships ``T x D`` activations instead of re-hosting the table).
* every block is one cut-point with analytically derived meta
  (``flops_fwd/flops_bwd/param_count/param_bytes/act_bytes/grad_bytes``),
  cross-checkable against the compiled HLO via
  :func:`hlo_crosscheck_flops` (``launch/hlo_analysis.loop_aware_cost``).
* ``head`` pins to the *stream end*: its output is the ``T x V`` logit
  tensor, which is why optimal schedules never cut after it.

Families (``block family`` labels used by benchmarks/tests):

* ``attention`` — ``dense`` decoder blocks (GQA + SwiGLU, local/global
  window pattern preserved per layer).
* ``moe``       — dense skeleton with routed-MoE MLPs.
* ``gla``       — Zamba2: Mamba2 (SSD) layers built on the chunked GLA
  primitive, ``h + Mamba2(RMSNorm(h))``, and at the hybrid layer ids
  (``hybrid_layer_ids``, else every ``shared_attn_every``-th) a
  ``hybrid`` cut point: application ``j`` of shared block
  ``j % num_mem_blocks`` reads ``[h ; e]`` (``e`` the token embedding),
  ``u = RMSNorm([h ; e])``, causal attention with RoPE and scale
  ``(hd / 2) ** -0.5`` from ``u`` to ``o`` (no residual), ``m =
  RMSNorm(o)``, ``[g | v] = m W_gate_up + (m A_j) B_j`` (the
  application's own rank-``adapter_rank`` adapter), ``t = (gelu(g) v)
  W_down L_j`` (its own linear), then its Mamba layer
  ``h + Mamba2(RMSNorm(h + t))``.  The stack starts at published layer
  ``first_layer``, so one pipeline stage is a config.  The embedding
  stream ``e`` rides with ``h`` as one activation, ``[b, T, 2 D] = [h ;
  e]``, between the embedding (which emits ``[e ; e]``) and the head
  (which reads ``h``): a cut ships both.  The cut-point protocol
  requires *disjoint per-cut params* (frontend copies are sliced as
  ``params[:m]`` and their gradients aggregated per cut), so a shared
  block applied more than once in one chain is **untied** here — each
  application is its own cut point with its own copy (summing the
  copies' gradients is out of scope).  The adapter is therefore its own
  reference model: the hybrid-vs-reference exactness suite runs both
  paths through this stack.
* ``xlstm``     — mLSTM blocks (GLA primitive) with an sLSTM block every
  ``slstm_every``-th position.

Unsupported: ``encdec`` (needs a second input stream) and VLM prefix
embeddings (``n_frontend_tokens > 0``) — the cut-point chain is strictly
linear.

Wire sizes: activations cross cuts in the model dtype (bf16 by default),
but gradients are exchanged in f32 (the weight-update phase of §IV-C
aggregates in full precision), so ``grad_bytes != act_bytes`` whenever the
compute dtype is narrower than f32 — the first profile family to exercise
the explicit ``MG`` channel of the cost model.

MoE caveat: ``apply_moe`` groups tokens (``group_size``); a sub-batch of
``b`` samples dispatches ``b*T`` tokens, which must be divisible by
``min(group_size, b*T)``.  Schedules used for *execution* (not just
scoring) should keep ``group_size >= B*T`` or a divisor relationship.
Capacity-dropping also makes routed MoE only *approximately* decomposable
across the hybrid batch split (which tokens drop depends on the group
composition); the hybrid step is exactly batch-B SGD whenever capacity is
lossless (``capacity_factor >= n_experts / 1``, i.e. no token ever
dropped) and within routing-drop noise otherwise.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.layerstack import CutMeta, LayerStack
from repro.models.lm import attention as attn_mod
from repro.models.lm import ssm as ssm_mod
from repro.models.lm import xlstm as xlstm_mod
from repro.models.lm.common import rms_norm, truncated_normal_init
from repro.models.lm.model import (LMConfig, _apply_block, _apply_norm,
                                   _group_layout, _init_block, _init_norm,
                                   _resid_hint)

Params = List[Any]

SUPPORTED_FAMILIES = ("dense", "moe", "zamba", "xlstm")

# cfg.family -> the block-family label used in benchmarks/docs.
FAMILY_LABELS = {"dense": "attention", "moe": "moe", "zamba": "gla",
                 "xlstm": "xlstm"}


@dataclasses.dataclass(frozen=True)
class _BlockSpec:
    kind: str   # embed | attn | moe | mamba2 | hybrid | mlstm | slstm | head
    window: int = 0    # attention window (0 = full) — attn blocks only
    block: int = 0     # hybrid: the shared block it applies


def _hybrid_applications(cfg: LMConfig) -> List[Tuple[int, int]]:
    """(published layer id, application index) of each hybrid layer of a
    zamba stack's layers ``first_layer .. first_layer + n_layers - 1``;
    application ``j`` applies shared block ``j % num_mem_blocks``."""
    end = cfg.first_layer + cfg.n_layers
    ids = cfg.hybrid_layer_ids or tuple(
        i for i in range(end) if i % cfg.shared_attn_every
        == cfg.shared_attn_every - 1)
    return [(i, j) for j, i in enumerate(ids) if cfg.first_layer <= i < end]


def _block_plan(cfg: LMConfig) -> List[_BlockSpec]:
    """The linear cut-point chain of one LM config."""
    if cfg.family not in SUPPORTED_FAMILIES:
        raise ValueError(
            f"family {cfg.family!r} has no LayerStack adapter "
            f"(supported: {SUPPORTED_FAMILIES})")
    if cfg.n_frontend_tokens > 0:
        raise ValueError("prefix-embedding (VLM/audio) configs are not "
                         "cut-point schedulable")
    plan = [_BlockSpec("embed")]
    if cfg.family in ("dense", "moe"):
        kind = "moe" if cfg.family == "moe" else "attn"
        ng, g, _ = _group_layout(cfg)
        for i in range(cfg.n_layers):
            # gemma3-style pattern: each group is (g-1) local + 1 global.
            is_global = ng > 0 and i < ng * g and i % g == g - 1
            plan.append(_BlockSpec(kind,
                                   0 if is_global else cfg.sliding_window))
    elif cfg.family == "zamba":
        assert cfg.ssm is not None
        assert cfg.hybrid_layer_ids or cfg.shared_attn_every > 0
        apps = dict(_hybrid_applications(cfg))
        for i in range(cfg.first_layer, cfg.first_layer + cfg.n_layers):
            plan.append(_BlockSpec("mamba2") if i not in apps else
                        _BlockSpec("hybrid",
                                   block=apps[i] % cfg.num_mem_blocks))
    else:  # xlstm
        assert cfg.xlstm is not None
        g = cfg.xlstm.slstm_every
        for i in range(cfg.n_layers):
            if g > 0 and i % g == g - 1:
                plan.append(_BlockSpec("slstm"))
            else:
                plan.append(_BlockSpec("mlstm"))
    plan.append(_BlockSpec("head"))
    return plan


# ---------------------------------------------------------------------------
# Analytic per-block meta (matmul FLOPs only — what the HLO dot-walker
# counts; elementwise ops ride along free at these arithmetic intensities).
# ---------------------------------------------------------------------------


def _norm_params(cfg: LMConfig) -> int:
    return 2 * cfg.d_model if cfg.norm == "layer" else cfg.d_model


def _attn_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    params = D * H * hd + 2 * D * KV * hd + H * hd * D
    if cfg.qkv_bias:
        params += H * hd + 2 * KV * hd
    # qkv + wo projections, then the dense (masked) T x T score/AV matmuls.
    flops = 2 * T * D * (H * hd) + 4 * T * D * (KV * hd) \
        + 4 * T * T * H * hd + 2 * T * (H * hd) * D
    return params, float(flops)


def _mlp_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    D, dff = cfg.d_model, cfg.d_ff
    if cfg.mlp == "gelu":
        return 2 * D * dff + dff + D, float(4 * T * D * dff)
    return 3 * D * dff, float(6 * T * D * dff)


def _moe_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    moe = cfg.moe
    assert moe is not None
    D = cfg.d_model
    E, K, F = moe.n_experts, moe.top_k, moe.d_ff_expert
    G = min(moe.group_size, T)          # nominal single-sample grouping
    C = max(int(G * K * moe.capacity_factor / E), 1)
    params = D * E + 3 * E * D * F
    # router + dispatch/combine einsums + expert SwiGLU + one-hot builds.
    per_tok = 2 * D * E + 4 * E * C * D + 6 * E * C * D * F / G \
        + 4 * K * E * C
    if moe.n_shared > 0:
        width = moe.d_ff_shared or moe.n_shared * F
        params += 3 * D * width
        per_tok += 6 * D * width
    return params, float(T * per_tok)


def _gla_flops(nh: int, dk: int, dv: int, W: int, T: int) -> float:
    """Chunked-GLA matmul FLOPs for T tokens: intra-chunk quadratic scores
    (2*W*dk) + intra AV (2*W*dv) + chunk-state build and query (4*dk*dv),
    per token per head."""
    return float(T * nh * (2 * W * (dk + dv) + 4 * dk * dv))


def _mamba2_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    sc = cfg.ssm
    assert sc is not None
    D = cfg.d_model
    di = ssm_mod.d_inner(D, sc)
    nh = ssm_mod.n_ssm_heads(D, sc)
    conv_ch = ssm_mod.conv_channels(D, sc)
    params = D * (di + conv_ch + nh) + sc.d_conv * conv_ch \
        + conv_ch + 3 * nh + di + di * D + _norm_params(cfg)
    W = min(sc.chunk, T)
    flops = 2 * T * D * (di + conv_ch + nh) \
        + 2 * T * sc.d_conv * conv_ch \
        + _gla_flops(nh, sc.d_state, sc.head_dim, W, T) \
        + 2 * T * di * D
    return params, float(flops)


def _hybrid_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    """The shared block over ``[h ; e]`` (full ``T x T`` scores), its
    adapter and linear, and the hybrid layer's Mamba layer."""
    D, H, hd, F, r = (cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff,
                      cfg.adapter_rank)
    A = H * hd
    params = 3 * 2 * D * A + A * D + 2 * D * F + F * D \
        + 2 * D + D + r * (D + 2 * F) + D * D
    flops = 2 * T * (3 * 2 * D * A + A * D + 2 * D * F + F * D
                     + r * (D + 2 * F) + D * D) + 4 * T * T * A
    pm, fm = _mamba2_meta(cfg, T)
    return params + pm, float(flops + fm)


def _mlstm_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    xc = cfg.xlstm
    assert xc is not None
    D = cfg.d_model
    di = xc.expand * D
    hd = di // xc.n_heads
    params = D * 2 * di + xc.d_conv * di + di + 3 * di * di \
        + di * 2 * xc.n_heads + 2 * xc.n_heads + di + di * D \
        + _norm_params(cfg)
    W = min(xc.chunk, T)
    flops = 2 * T * D * 2 * di + 2 * T * xc.d_conv * di \
        + 6 * T * di * di + 2 * T * di * 2 * xc.n_heads \
        + _gla_flops(xc.n_heads, hd, hd, W, T) \
        + 2 * T * di * D
    return params, float(flops)


def _slstm_meta(cfg: LMConfig, T: int) -> Tuple[int, float]:
    xc = cfg.xlstm
    assert xc is not None
    D = cfg.d_model
    hd = D // xc.n_heads
    params = D * 4 * D + xc.n_heads * hd * 4 * hd + 4 * D + D + D * D \
        + _norm_params(cfg)
    # input projection + per-step recurrent matmul + output projection.
    flops = 2 * T * D * 4 * D + 8 * T * D * hd + 2 * T * D * D
    return params, float(flops)


# ---------------------------------------------------------------------------
# Zamba2's layers over the [h ; e] stream.
# ---------------------------------------------------------------------------


def start_stream(e: jax.Array) -> jax.Array:
    """The embedding's output on a zamba stack: ``[h ; e]`` with ``h =
    e``."""
    return jnp.concatenate([e, e], axis=-1)


def _init_hybrid(key: jax.Array, cfg: LMConfig) -> Params:
    D, H, hd, F, r = (cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff,
                      cfg.adapter_rank)
    kq, kk, kv, ko, kg, kd, kr, kb, kl, km = jax.random.split(key, 10)
    tn = lambda k, shape: truncated_normal_init(k, shape, 1.0, cfg.dtype)
    p = {"shared": {
             "ln_in": {"w": jnp.zeros((2 * D,), cfg.dtype)},
             "attn": {"wq": tn(kq, (2 * D, H * hd)),
                      "wk": tn(kk, (2 * D, H * hd)),
                      "wv": tn(kv, (2 * D, H * hd)),
                      "wo": tn(ko, (H * hd, D))},
             "ln_mlp": _init_norm(cfg),
             "mlp": {"w_gate_up": tn(kg, (D, 2 * F)),
                     "w_down": tn(kd, (F, D))}},
         "linear": tn(kl, (D, D)),
         "pre": _init_norm(cfg),
         "m": ssm_mod.init_mamba2(km, D, cfg.ssm, cfg.dtype)}
    if r:
        p["adapter"] = {"down": tn(kr, (D, r)), "up": tn(kb, (r, 2 * F))}
    return p


def adapt(p: Params, m: jax.Array) -> jax.Array:
    """The application's own low-rank term of the shared MLP's
    ``[gate | up]``."""
    with obs.scope("adapter"):
        return (m @ p["down"]) @ p["up"]


def _shared_block(cfg: LMConfig, p: Params, adapter, x: jax.Array
                  ) -> jax.Array:
    """Zamba2's shared transformer block on ``x = [h ; e]``: attention,
    then the gated-GELU MLP, each after an RMSNorm, neither with a
    residual of its own."""
    eps = cfg.rms_norm_eps
    u = rms_norm(x, p["ln_in"]["w"], eps)
    o = attn_mod.self_attention(
        p["attn"], u, n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
        head_dim=cfg.hd, causal=True, rope_theta=cfg.rope_theta,
        use_flash=cfg.use_flash, block_q=cfg.attn_block_q,
        scale=(cfg.hd / 2) ** -0.5)
    m = _apply_norm(cfg, p["ln_mlp"], o)
    gu = m @ p["mlp"]["w_gate_up"]
    if adapter is not None:
        gu = gu + adapt(adapter, m)
    g, v = jnp.split(gu, 2, axis=-1)
    g = jax.nn.gelu(g.astype(jnp.float32), approximate=False)
    return (g.astype(v.dtype) * v) @ p["mlp"]["w_down"]


def _apply_zamba(cfg: LMConfig, kind: str, block: int, p: Params,
                 x: jax.Array) -> jax.Array:
    """A mamba2 or hybrid layer on ``x = [h ; e]``; ``e`` passes
    through."""
    D = cfg.d_model
    x = _resid_hint(cfg, x)
    h = x[..., :D]
    hin = h
    if kind == "hybrid":
        with obs.scope(f"shared{block}"):
            t = _shared_block(cfg, p["shared"], p.get("adapter"), x)
        hin = h + t @ p["linear"]
    y = _remat_mixer(lambda pm, u: ssm_mod.apply_mamba2(
        pm, u, cfg.ssm, use_kernel=cfg.use_gla_kernel,
        eps=cfg.rms_norm_eps))(p["m"], _apply_norm(cfg, p["pre"], hin))
    return jnp.concatenate([h + y, x[..., D:]], axis=-1)


def _remat_mixer(fn):
    """The Mamba2 mixer keeps only its matmuls' outputs for the backward
    and computes the rest again there (conv, gates, the scan's forward,
    the gated norm): that chain holds several f32 ``[b, T, d_inner]``
    arrays per layer.  For the stage of Zamba2-7B's layers 18-23 at
    T = 4096, B = 2 the step's temporaries go from 12.7 to 8.1 GiB of a
    v5e's 15.75 (compile estimate for a described chip)."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)


# ---------------------------------------------------------------------------
# The adapter.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LMLayerStack(LayerStack):
    """An LM config's block stack behind the :class:`LayerStack` protocol.

    ``seq_len`` fixes the per-*sample* meta: one sample is one sequence of
    ``seq_len`` tokens (tokens + targets = ``8 * seq_len`` wire bytes), so
    the HierTrain batch axis is the sequence axis and every schedule's
    ``b_*`` counts sequences.
    """
    cfg: LMConfig
    seq_len: int
    backend: str = "ref"

    def __post_init__(self) -> None:
        if self.backend not in ("ref", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}; pick "
                             f"'ref' or 'pallas'")
        if self.backend == "pallas":
            # Route apply_segment's attention blocks onto the Pallas
            # flash-attention kernel and the mamba2/mLSTM blocks onto the
            # chunked GLA scan (kernels/ops.py; interpret mode off-TPU).
            # Analytic cut meta is backend-independent, so profiles and
            # schedules are identical — only kernel numerics differ,
            # within the oracle suite's pinned tolerance.
            self.cfg = self.cfg.variant(use_flash=True, use_gla_kernel=True)
        self._plan = _block_plan(self.cfg)

    @property
    def name(self) -> str:                        # type: ignore[override]
        return f"{self.cfg.name}@T{self.seq_len}"

    @property
    def family(self) -> str:
        return FAMILY_LABELS[self.cfg.family]

    @property
    def num_layers(self) -> int:
        return len(self._plan)

    # ---- metadata ------------------------------------------------------

    def cut_meta(self) -> List[CutMeta]:
        cfg, T = self.cfg, self.seq_len
        act_elem = jnp.dtype(cfg.dtype).itemsize
        # zamba cuts carry [h ; e]: twice the hidden width.
        hid_elems = float(T * cfg.d_model * (2 if cfg.family == "zamba"
                                             else 1))
        hid_act = hid_elems * act_elem
        hid_grad = hid_elems * 4                       # f32 gradient wire
        metas: List[CutMeta] = []
        counts = {k: 0 for k in ("attn", "moe", "mamba2", "hybrid", "mlstm",
                                  "slstm")}
        for spec in self._plan:
            if spec.kind == "embed":
                metas.append(CutMeta(
                    name="embed", param_count=cfg.vocab * cfg.d_model,
                    flops_fwd=0.0, flops_bwd=0.0,
                    act_bytes=hid_act, grad_bytes=hid_grad,
                    act_elems=hid_elems, grad_elems=hid_elems,
                    param_bytes=float(cfg.vocab * cfg.d_model * act_elem)))
                continue
            if spec.kind == "head":
                p = cfg.d_model * cfg.vocab + _norm_params(cfg)
                flops = float(2 * T * cfg.d_model * cfg.vocab)
                metas.append(CutMeta(
                    name="head", param_count=p, flops_fwd=flops,
                    flops_bwd=2.0 * flops,
                    act_bytes=float(T * cfg.vocab * act_elem),
                    grad_bytes=float(T * cfg.vocab * 4),
                    act_elems=float(T * cfg.vocab),
                    grad_elems=float(T * cfg.vocab),
                    param_bytes=float(p * act_elem)))
                continue
            if spec.kind == "attn":
                pa, fa = _attn_meta(cfg, T)
                pm, fm = _mlp_meta(cfg, T)
                p, flops = pa + pm + 2 * _norm_params(cfg), fa + fm
            elif spec.kind == "moe":
                pa, fa = _attn_meta(cfg, T)
                pm, fm = _moe_meta(cfg, T)
                p, flops = pa + pm + 2 * _norm_params(cfg), fa + fm
            elif spec.kind == "mamba2":
                p, flops = _mamba2_meta(cfg, T)
            elif spec.kind == "hybrid":
                p, flops = _hybrid_meta(cfg, T)
            elif spec.kind == "mlstm":
                p, flops = _mlstm_meta(cfg, T)
            else:
                p, flops = _slstm_meta(cfg, T)
            counts[spec.kind] += 1
            metas.append(CutMeta(
                name=f"{spec.kind}{counts[spec.kind]}", param_count=p,
                flops_fwd=flops, flops_bwd=2.0 * flops,
                act_bytes=hid_act, grad_bytes=hid_grad,
                act_elems=hid_elems, grad_elems=hid_elems,
                param_bytes=float(p * act_elem)))
        return metas

    def default_sample_bytes(self) -> float:
        return 8.0 * self.seq_len        # int32 tokens + int32 targets

    # ---- params --------------------------------------------------------

    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, len(self._plan))
        params: Params = []
        for spec, k in zip(self._plan, keys):
            if spec.kind == "embed":
                params.append({"embed": truncated_normal_init(
                    k, (cfg.vocab, cfg.d_model), 1.0, cfg.dtype)})
            elif spec.kind == "head":
                params.append({
                    "final_norm": _init_norm(cfg),
                    "lm_head": truncated_normal_init(
                        k, (cfg.d_model, cfg.vocab), 1.0, cfg.dtype)})
            elif spec.kind in ("attn", "moe"):
                params.append(_init_block(k, cfg))
            elif spec.kind == "mamba2":
                params.append({"pre": _init_norm(cfg),
                               "m": ssm_mod.init_mamba2(
                                   k, cfg.d_model, cfg.ssm, cfg.dtype)})
            elif spec.kind == "hybrid":
                params.append(_init_hybrid(k, cfg))
            elif spec.kind == "mlstm":
                params.append({"pre": _init_norm(cfg),
                               "m": xlstm_mod.init_mlstm(
                                   k, cfg.d_model, cfg.xlstm, cfg.dtype)})
            else:
                params.append({"pre": _init_norm(cfg),
                               "s": xlstm_mod.init_slstm(
                                   k, cfg.d_model, cfg.xlstm, cfg.dtype)})
        return params

    # ---- execution -----------------------------------------------------

    def apply_segment(self, params: Params, x: jax.Array, start: int,
                      stop: int) -> jax.Array:
        """Layers ``start..stop-1``, each under the named scope
        ``layer{i}.{kind}`` (``repro.obs.scope``)."""
        for i in range(start, stop):
            with obs.scope(f"layer{i}.{self._plan[i].kind}"):
                x = self._apply_layer(i, params[i], x)
        return x

    def _apply_layer(self, i: int, p, x: jax.Array) -> jax.Array:
        cfg, kind = self.cfg, self._plan[i].kind
        zamba = cfg.family == "zamba"
        if kind == "embed":
            e = jnp.take(p["embed"], x, axis=0)
            return start_stream(e) if zamba else e
        if kind == "head":
            if zamba:
                x = x[..., :cfg.d_model]
            return _apply_norm(cfg, p["final_norm"], x) @ p["lm_head"]
        if kind in ("attn", "moe"):
            return _apply_block(cfg, p, x, self._plan[i].window)
        if zamba:
            return _apply_zamba(cfg, kind, self._plan[i].block, p, x)
        h = _resid_hint(cfg, x)
        hn = _apply_norm(cfg, p["pre"], h)
        if kind == "mlstm":
            return h + xlstm_mod.apply_mlstm(p["m"], hn, cfg.xlstm,
                                             use_kernel=cfg.use_gla_kernel,
                                             eps=cfg.rms_norm_eps)
        return h + xlstm_mod.apply_slstm(p["s"], hn, cfg.xlstm,
                                         eps=cfg.rms_norm_eps)

    def sum_loss(self, logits: jax.Array, labels: jax.Array) -> jax.Array:
        """Per-sequence-sum token cross-entropy (f32), under the named
        scope ``loss``."""
        with obs.scope("loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None],
                                       axis=-1)[..., 0]
            return jnp.sum(nll)

    def dummy_batch(self, key: jax.Array, batch: int
                    ) -> Tuple[jax.Array, jax.Array]:
        kx, ky = jax.random.split(key)
        x = jax.random.randint(kx, (batch, self.seq_len), 0, self.cfg.vocab)
        y = jax.random.randint(ky, (batch, self.seq_len), 0, self.cfg.vocab)
        return x, y


def lm_layerstack(cfg: LMConfig, seq_len: int,
                  backend: str = "ref") -> LMLayerStack:
    """Build the LayerStack adapter over ``cfg``'s block stack.

    ``backend="pallas"`` routes attention blocks onto
    ``kernels/flash_attention.py`` and GLA-family blocks (mamba2/mLSTM)
    onto ``kernels/gla_scan.py``; ``"ref"`` (default) keeps the pure-jnp
    reference path that ``kernels/ref.py``-style oracles pin.  Profiles
    and schedules are backend-independent."""
    return LMLayerStack(cfg=cfg, seq_len=seq_len, backend=backend)


# ---------------------------------------------------------------------------
# HLO cross-check: compile one cut-point's forward segment and count its
# dot FLOPs with the loop-aware HLO walker — the guard that keeps the
# analytic meta honest as block implementations evolve.
# ---------------------------------------------------------------------------


def hlo_block_flops(stack: LMLayerStack, cut: int, batch: int = 1) -> float:
    """Measured per-sample matmul FLOPs of cut-point ``cut`` (compiled)."""
    from repro.launch.hlo_analysis import loop_aware_cost
    params = stack.init(jax.random.PRNGKey(0))
    x, _ = stack.dummy_batch(jax.random.PRNGKey(1), batch)
    # repro-lint: disable-next=RA102 one-shot HLO probe, compiled once per crosscheck
    xi = x if cut == 0 else jax.jit(
        lambda p, v: stack.apply_segment(p, v, 0, cut))(params, x)
    fn = jax.jit(lambda p, v: stack.apply_segment(p, v, cut, cut + 1))
    hlo = fn.lower(params, xi).compile().as_text()
    flops, _, _ = loop_aware_cost(hlo)
    return float(flops) / batch


def hlo_crosscheck_flops(stack: LMLayerStack, cut: int, batch: int = 1
                         ) -> Tuple[float, float]:
    """(analytic, hlo-measured) per-sample forward FLOPs of one cut."""
    analytic = stack.cut_meta()[cut].flops_fwd
    return analytic, hlo_block_flops(stack, cut, batch)
