"""Train-step builders for the LM runtime.

Two gradient-sync modes:

* ``hier_sync=False`` — classic synchronous data parallelism: the batch
  is sharded over ``(pod, data)`` and XLA's SPMD partitioner emits the
  full cross-replica all-reduce (this is the paper's "horizontal
  training" baseline, Fig. 1a, at pod scale).
* ``hier_sync=True`` — HierTrain hybrid parallelism over the pod axis:
  ``jax.shard_map`` keeps ``pod`` manual (each pod computes gradients on
  its own batch shard, auto-sharded over ``data``/``model`` inside), and
  the cross-pod reduction is the *tiered* sync — frontend tiers pmean at
  full width over the DCN, backend (parameter-heavy) tiers cross int8-
  quantized.  Intra-pod ICI reductions stay automatic, exactly the
  paper's cheap-WLAN assumption.

Microbatching (gradient accumulation) reshapes the batch to
``[k, B/k, ...]`` and lax.scans the grad computation with an f32
accumulator — per-chip activation memory drops k-fold while the HLO
stays one fused loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distrib.tiered_sync import TierAssignment, tiered_grad_sync
from repro.optim.optimizers import Optimizer

Tree = Any
TrainState = Dict[str, Tree]        # {"params": ..., "opt": ...}


def init_state(model, optimizer: Optimizer, key: jax.Array) -> TrainState:
    params = model.init(key)
    return {"params": params, "opt": optimizer.init(params)}


def _microbatched_grads(loss_fn: Callable, params: Tree, batch: Tree,
                        microbatches: int) -> Tuple[jax.Array, Tree]:
    if microbatches <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    from repro.models.lm.common import shard_hint

    def resh(x):
        x = x.reshape((microbatches, x.shape[0] // microbatches)
                      + x.shape[1:])
        # keep the per-microbatch batch dim on the DP axes — without this
        # XLA is free to re-shard onto the sequence dim and store
        # full-batch residuals (measured 8x per-device activation memory).
        return shard_hint(x, None, ("pod", "data"),
                          *([None] * (x.ndim - 2)))

    mb = jax.tree.map(resh, batch)

    def body(carry, b):
        loss_acc, grad_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, b)
        grad_acc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), grad_acc, grads)
        return (loss_acc + loss, grad_acc), None

    # init the accumulator *from* the params so it inherits their sharding
    # (a bare zeros() would let XLA replicate ~GBs of f32 per device).
    zeros = jax.tree.map(
        lambda p: (p * 0).astype(jnp.float32), params)
    carry0 = (jnp.zeros((), jnp.float32), zeros)
    (loss, grads), _ = jax.lax.scan(body, carry0, mb)
    inv = 1.0 / microbatches
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def make_train_step(model, optimizer: Optimizer, *,
                    microbatches: int = 1,
                    hier_sync: bool = False,
                    tiers: Optional[TierAssignment] = None,
                    donate: bool = True) -> Callable:
    """Returns ``train_step(state, batch, key) -> (state, metrics)``.

    ``hier_sync`` requires a mesh with a ``pod`` axis in scope at lower
    time; ``tiers=None`` under hier_sync is the paper-faithful variant
    (all tiers full-width over the pod axis — still manual, so the DCN
    traffic is explicit in the HLO rather than fused into one global
    all-reduce).
    """
    loss_fn = model.loss_fn

    def _grads(params, batch):
        return _microbatched_grads(loss_fn, params, batch, microbatches)

    def plain_step(state: TrainState, batch: Tree, key: jax.Array):
        loss, grads = _grads(state["params"], batch)
        params, opt, gnorm = optimizer.update(state["params"], grads,
                                              state["opt"])
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": opt["step"]}
        return {"params": params, "opt": opt}, metrics

    def hier_step(state: TrainState, batch: Tree, key: jax.Array):
        def per_pod(params, b, k):
            k = jax.random.fold_in(k, jax.lax.axis_index("pod"))
            loss, grads = _grads(params, b)
            grads = tiered_grad_sync(grads, tiers, k, axis="pod")
            return jax.lax.pmean(loss, "pod"), grads

        # check_vma=False: the model body is full of scans whose carries
        # start as unvarying constants (loss chunks, GLA states, grad
        # accumulators) — strict varying-manual-axis typing would need a
        # pcast at every one of them.
        loss, grads = jax.shard_map(
            per_pod,
            in_specs=(P(), P("pod"), P()),
            out_specs=(P(), P()),
            axis_names={"pod"},
            check_vma=False,
        )(state["params"], batch, key)
        params, opt, gnorm = optimizer.update(state["params"], grads,
                                              state["opt"])
        metrics = {"loss": loss, "grad_norm": gnorm, "step": opt["step"]}
        return {"params": params, "opt": opt}, metrics

    return hier_step if hier_sync else plain_step
