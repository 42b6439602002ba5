"""Chip smoke test: the HierTrain hybrid-parallel training step on TPU.

Drives the main path once through its public entry points, at the
published widths of Zamba2-7B (arXiv:2411.15242; d_model 3584, vocab
32000, Mamba2 112 heads x 64 in 2 B/C groups, d_state 64, chunk 256;
the shared block's attention 32 heads x 224 over [h ; embedding], gated
GELU 14336, adapter rank 128) cut to one whole period of its layer
pattern, the pipeline stage of published layers 18-23
(``FULL.variant(n_layers=6, first_layer=18)``: embed, 5 mamba2, 1
hybrid layer that applies shared block 1, head; 1.05 B params), with
random weights from a seed and ``backend="pallas"`` so both Pallas
kernels (flash attention, GLA scan) sit on the path.

One chip (no arguments):

1. ``plan(stack, Fleet.lm_default(m=2), B)`` -> ``Plan.step_fn()`` for
   ``PLAN_STEPS`` steps; every loss must be finite.
2. ``Plan.train(data, steps=3)``.
3. The split step the planner never picks at these widths: half the
   batch on a device stream whose front segment is embed + the first
   two mamba2 layers, merged into the cloud tail
   (``jitted_multi_hybrid_step`` / ``multi_split_batch``), against
   ``jitted_reference_step`` on the same params and batch.
4. Every compiled step must contain ``tpu_custom_call`` (kernels did
   not fall back to interpret mode).

``--chips 4`` runs only the sharded cloud tail: the same split on a
tree fleet, with the cloud tail data-parallel over a 4-chip mesh
(``cloud_mesh=``), against the same step with ``cloud_mesh=None`` on
one chip.

Sizes.  Compile rehearsal for a described v5e (16 GB HBM, 15.75 GiB
usable) of the stage's planned step at T = 4096, B = 2,
``memory_analysis()``: parameters 1.96 GiB,
temporaries 8.08 GiB (12.68 without the Mamba mixers' recomputation,
``layerstack._remat_mixer``).  So the one-chip phases run T = 4096,
B = 2.  The four-chip phase needs
B divisible by the 4 data-parallel shards and an unsharded step of the
same B on one chip, which B = 4 at T = 4096 does not fit, so it runs
T = 2048, B = 4.

Times printed here are host-clock wall times around steps that end in
``block_until_ready``: a smoke check, not a benchmark.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase raises, exits non-zero and prints no such line.

Usage::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded cloud tail on four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
T_ONE, B_ONE = 4096, 2
T_FOUR, B_FOUR = 2048, 4
PLAN_STEPS = 3
TRAIN_STEPS = 3
# Multi-step phases: a per-sequence-sum loss over 4096 tokens has large
# gradients, and bf16 weights; keep three steps well inside the range
# where the loss stays finite.
LR_TRAIN = 1e-4
# Split-vs-reference check: one step from the initial weights, with a
# learning rate large enough that the bf16 weight updates span many
# ulps of the weights they change (the check compares those updates).
LR_CHECK = 0.05
FRONT_CUT = 3            # embed + mamba2 #18 + mamba2 #19
# Loss: both paths run the same per-sample forward over the same samples
# in the same order, but the front segment runs at batch B/2 on each
# stream instead of B, and the compiler may tile and round those bf16
# matmuls differently (2**-8 relative per rounding).  On the CPU backend
# at a cut width that moved the summed loss by 8e-5 relative.  A dropped
# or misrouted sample moves it by O(1), so 1e-3 still catches one.
LOSS_RTOL = 1e-3
# Updates, per cut point, dW = W_new - W_old in f32:
#   ||dW_split - dW_ref|| <= UPDATE_RTOL * ||dW_ref|| + ||ulp(W_new)||.
# The first term: the backward pass rounds its activation cotangents to
# bf16 (2**-8 relative) at every layer, and the two programs batch and
# sum them differently (two streams, or four shards, against one), so
# the difference grows with depth below the head.  On the CPU backend at
# a cut width it grew from 1% at the head to 2.5% at the embedding; 0.05
# leaves twice that.  The second: each
# step rounds its new weight to bf16 (half an ulp each, so at most one
# ulp apart), which can exceed the first term where an update is small
# next to its weight.  Dropping one stream's gradient removes about half
# of a front layer's update, so the run without the device stream must
# miss the same bound by at least DROP_FACTOR times.
UPDATE_RTOL = 0.05
DROP_FACTOR = 4.0


def log(msg: str) -> None:
    print(msg, flush=True)


def _bootstrap():
    """Put ``src`` on the path, keep the compile cache where the
    environment says (or at ``<repo>/.jax_cache``) and refuse to run off
    a TPU.  Returns the jax module."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no repro package under {src}; run this "
                 f"script from a checkout of the repository")
    sys.path.insert(0, src)
    import jax

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache(REPO)
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    log(f"compile cache: {cache}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform "
                 f"{d.platform!r} ({d.device_kind}); not running on it")
    return jax


def _stack(T: int):
    from repro.configs import zamba2_7b
    from repro.models.lm.layerstack import lm_layerstack
    cfg = zamba2_7b.FULL.variant(n_layers=6, first_layer=18)
    return lm_layerstack(cfg, seq_len=T, backend="pallas")


def _compile(jax, name: str, fn, *args):
    """Lower + compile ``fn`` for ``args``; report compile seconds and
    the memory analysis, and require a Pallas kernel in the program."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    n_kernel = compiled.as_text().count("tpu_custom_call")
    log(f"[{name}] compile {dt:.3f}s (host clock)  memory: "
        f"arguments {ma.argument_size_in_bytes} B, outputs "
        f"{ma.output_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B, "
        f"temporaries {ma.temp_size_in_bytes} B  tpu_custom_call x{n_kernel}")
    if n_kernel == 0:
        raise SystemExit(f"[{name}] compiled step has no tpu_custom_call: "
                         "the Pallas kernels did not compile for the chip")
    return compiled


def _finite(jax, name: str, loss) -> float:
    v = float(jax.device_get(loss))
    if v != v or v in (float("inf"), float("-inf")):
        raise SystemExit(f"[{name}] loss is not finite: {v}")
    return v


def _cut_update_errors(jax, stack, old, new_a, new_b):
    """Per cut point: (ratio, ||dB||) with d = new - old in f32 and
    ``ratio = ||dA - dB|| / (UPDATE_RTOL * ||dB|| + ||ulp(new_b)||)``;
    the updates agree when ratio <= 1."""
    import jax.numpy as jnp

    @jax.jit
    def sq(o, a, b):
        da = a.astype(jnp.float32) - o.astype(jnp.float32)
        db = b.astype(jnp.float32) - o.astype(jnp.float32)
        ulp = jnp.abs(jnp.spacing(b)).astype(jnp.float32)
        return jnp.sum((da - db) ** 2), jnp.sum(db ** 2), jnp.sum(ulp ** 2)

    out = []
    for i in range(stack.num_layers):
        num = den = rnd = 0.0
        for o, a, b in zip(jax.tree.leaves(old[i]), jax.tree.leaves(new_a[i]),
                           jax.tree.leaves(new_b[i])):
            n, d, r = (float(v) for v in jax.device_get(sq(o, a, b)))
            num, den, rnd = num + n, den + d, rnd + r
        bound = UPDATE_RTOL * den ** 0.5 + rnd ** 0.5
        out.append((num ** 0.5 / bound if bound > 0 else float("inf"),
                    den ** 0.5))
    return out


def _split_schedule(B: int):
    from repro.core.cost_model import MultiSchedule
    h = B // 2
    return MultiSchedule(worker_o="cloud", worker_l="edge",
                         s_workers=("device_0", "device_1"),
                         m_s=(FRONT_CUT, 0), m_l=FRONT_CUT, b_o=B - h,
                         b_s=(h, 0), b_l=0)


def one_chip(jax) -> None:
    from repro.api import Fleet, plan
    from repro.core import hybrid_step as hs

    T, B = T_ONE, B_ONE
    stack = _stack(T)
    key = jax.random.PRNGKey(SEED)
    x, y = stack.dummy_batch(jax.random.fold_in(key, 1), B)
    shapes = jax.eval_shape(stack.init, key)
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(shapes))
    log(f"model: {stack.name} cut points {stack.num_layers} "
        f"({', '.join(m.name for m in stack.cut_meta())}); "
        f"{n_params} params; T={T} B={B}")

    # -- 1. planned step -------------------------------------------------
    p = plan(stack, Fleet.lm_default(m=2), B)
    sched = p.schedule
    log(f"[plan] {p.fleet.describe()}: {sched.describe()}")
    _compile(jax, "plan", hs.jitted_multi_hybrid_step(
        stack, sched.m_s, sched.m_l, LR_TRAIN, wire=p.wire),
        shapes, hs.multi_split_batch(x, y, sched))
    step = p.step_fn(lr=LR_TRAIN)
    params = p.init_params(key)
    times, losses = [], []
    for _ in range(PLAN_STEPS):
        t0 = time.perf_counter()
        params, loss = step(params, x, y)
        jax.block_until_ready((params, loss))
        times.append(time.perf_counter() - t0)
        losses.append(_finite(jax, "plan", loss))
    log(f"[plan] losses {losses}")
    log(f"[plan] step wall times (host clock, not a benchmark; the first "
        f"includes dispatch of a freshly compiled program): {times}")
    del params

    # -- 2. trainer --------------------------------------------------------
    class Data:
        """Seeded token batches, generated on the device."""

        def batch(self, i):
            xb, yb = stack.dummy_batch(jax.random.fold_in(key, 100 + i), B)
            return {"x": xb, "labels": yb}

    t0 = time.perf_counter()
    out = p.train(Data(), steps=TRAIN_STEPS, lr=LR_TRAIN, seed=SEED)
    jax.block_until_ready(out["params"])
    train_losses = [h["loss"] for h in out["history"]]
    for v in train_losses:
        _finite(jax, "train", v)
    log(f"[train] {TRAIN_STEPS} steps in {time.perf_counter() - t0:.3f}s "
        f"(host clock): losses {train_losses}, final schedule "
        f"{out['final_schedule'].describe()}")
    del out

    # -- 3. split step vs reference ----------------------------------------
    split = _split_schedule(B)
    log(f"[split] fixed schedule {split.describe()}")
    split_fn = hs.jitted_multi_hybrid_step(stack, split.m_s, split.m_l,
                                           LR_CHECK)
    ref_fn = hs.jitted_reference_step(stack, LR_CHECK)
    batches = hs.multi_split_batch(x, y, split)
    _compile(jax, "split", split_fn, shapes, batches)
    _compile(jax, "reference", ref_fn, shapes, x, y)
    t0 = time.perf_counter()
    new_split, loss_split = split_fn(stack.init(key), batches)
    jax.block_until_ready(new_split)
    t_split = time.perf_counter() - t0
    t0 = time.perf_counter()
    new_ref, loss_ref = ref_fn(stack.init(key), x, y)
    jax.block_until_ready(new_ref)
    t_ref = time.perf_counter() - t0
    ls, lr_ = _finite(jax, "split", loss_split), _finite(jax, "ref", loss_ref)
    log(f"[split] step {t_split:.3f}s, reference {t_ref:.3f}s (host clock; "
        f"first call of each program, incl. weight init)")
    rel = abs(ls - lr_) / abs(lr_)
    log(f"[split] loss split {ls!r} reference {lr_!r} rel diff {rel:.3e} "
        f"(bound {LOSS_RTOL})")
    old = stack.init(key)
    errs = _cut_update_errors(jax, stack, old, new_split, new_ref)
    names = [m.name for m in stack.cut_meta()]
    for n, (e, norm) in zip(names, errs):
        log(f"[split] {n:8s} update error / bound {e:.3e}  "
            f"||dW_ref|| {norm:.4e}")
    del new_split

    # The run without the device stream's gradient: the front layers'
    # update is then the o-stream's alone, which the reference step on
    # the o-stream's samples at half the rate reproduces (B/2 = b_o).
    drop_fn = hs.jitted_reference_step(stack, LR_CHECK * split.b_o / B)
    new_drop, _ = drop_fn(stack.init(key), x[:split.b_o], y[:split.b_o])
    drop = _cut_update_errors(jax, stack, old, new_drop, new_ref)
    for n, (e, _) in zip(names[:FRONT_CUT], drop[:FRONT_CUT]):
        log(f"[split] without the device stream: {n:8s} update error / "
            f"bound {e:.3e}")
    del new_drop, new_ref, old

    if rel > LOSS_RTOL:
        raise SystemExit(f"[split] loss differs from the reference by "
                         f"{rel:.3e} > {LOSS_RTOL}")
    worst = max(e for e, _ in errs)
    if worst > 1.0:
        raise SystemExit(f"[split] updates differ from the reference by "
                         f"{worst:.3e} x the bound")
    least_drop = min(e for e, _ in drop[:FRONT_CUT])
    if least_drop < DROP_FACTOR:
        raise SystemExit(f"[split] dropping the device stream's gradient "
                         f"misses the bound by only {least_drop:.3e}x; the "
                         f"check would not catch it")
    log(f"[split] agrees with the reference: worst update error "
        f"{worst:.3e} x the bound; without the device stream the front "
        f"misses it by >= {least_drop:.3e}x")


def four_chips(jax) -> None:
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api import Fleet, plan
    from repro.core import hybrid_step as hs
    from repro.core.fleet import LM_RAW_SAMPLE_BYTES
    from repro.core.profiler import LM_TESTBED

    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devs)}")
    T, B = T_FOUR, B_FOUR
    stack = _stack(T)
    key = jax.random.PRNGKey(SEED)
    x, y = stack.dummy_batch(jax.random.fold_in(key, 1), B)
    fleet = Fleet(workers=LM_TESTBED, device_slowdowns=(1.0, 1.4),
                  uplink_mbps=(50.0, 40.0), backhaul_mbps=200.0,
                  sample_bytes=LM_RAW_SAMPLE_BYTES, topology="tree",
                  edge_of=(0, 0))
    p = plan(stack, fleet, B)
    split = _split_schedule(B)
    edges = hs.tree_stream_edges(p.profile, p.network, split)
    log(f"[mesh] {stack.name} T={T} B={B} on {fleet.describe()}; fixed "
        f"schedule {split.describe()} stream edges {edges}")
    mesh = Mesh(np.array(devs), ("data",), axis_types=(AxisType.Auto,))
    repl = NamedSharding(mesh, P())
    batches = hs.multi_split_batch(x, y, split)

    sharded = hs.jitted_tree_hybrid_step(stack, split.m_s, split.m_l,
                                         LR_CHECK, stream_edge=edges,
                                         cloud_mesh=mesh)
    plain = hs.jitted_tree_hybrid_step(stack, split.m_s, split.m_l,
                                       LR_CHECK, stream_edge=edges)
    shapes = jax.eval_shape(stack.init, key)
    params_mesh = jax.device_put(stack.init(key), repl)
    batches_mesh = jax.device_put(batches, repl)
    c = _compile(jax, "mesh sharded", sharded, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        shapes), batches_mesh)
    txt = c.as_text()
    if "num_partitions=4" not in txt or "all-reduce" not in txt:
        raise SystemExit("[mesh] the sharded step is not one program over "
                         "4 partitions with an all-reduce")
    t0 = time.perf_counter()
    new_sh, loss_sh = sharded(params_mesh, batches_mesh)
    jax.block_until_ready(new_sh)
    log(f"[mesh] sharded step {time.perf_counter() - t0:.3f}s (host clock)")
    placed = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(new_sh)}
    if placed != {4}:
        raise SystemExit(f"[mesh] updated params span {placed} devices, "
                         "expected all 4")
    # Gather the sharded result to chip 0 before the unsharded step runs
    # there, so each chip holds at most two weight copies.
    new_sh = jax.device_put(new_sh, devs[0])
    del params_mesh, batches_mesh

    _compile(jax, "mesh unsharded", plain, shapes, batches)
    t0 = time.perf_counter()
    new_pl, loss_pl = plain(stack.init(key), batches)
    jax.block_until_ready(new_pl)
    log(f"[mesh] unsharded step {time.perf_counter() - t0:.3f}s "
        f"(host clock)")
    ls, lp = _finite(jax, "mesh", loss_sh), _finite(jax, "mesh", loss_pl)
    rel = abs(ls - lp) / abs(lp)
    log(f"[mesh] loss sharded {ls!r} unsharded {lp!r} rel diff {rel:.3e} "
        f"(bound {LOSS_RTOL})")
    errs = _cut_update_errors(jax, stack, stack.init(key), new_sh, new_pl)
    for n, (e, norm) in zip((m.name for m in stack.cut_meta()), errs):
        log(f"[mesh] {n:8s} update error / bound {e:.3e}  ||dW|| "
            f"{norm:.4e}")
    worst = max(e for e, _ in errs)
    if rel > LOSS_RTOL or worst > 1.0:
        raise SystemExit(f"[mesh] sharded and unsharded steps disagree: "
                         f"loss {rel:.3e}, update {worst:.3e}")
    log(f"[mesh] sharded == unsharded: worst update error {worst:.3e} x "
        f"the bound")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: planned step, trainer and split-vs-reference "
                         "check; 4: sharded cloud tail vs unsharded only")
    args = ap.parse_args(argv)
    jax = _bootstrap()
    if args.chips == 4:
        four_chips(jax)
    else:
        one_chip(jax)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
