import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Everything above this line runs before ANY other import: jax locks the
# device count at first initialization, and the production meshes below
# need 512 placeholder host devices.
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_arch, input_specs  # noqa: E402
from repro.distrib import (batch_shardings, cache_shardings,  # noqa: E402
                           choose_tiers, opt_state_shardings,
                           param_shardings)
from repro.distrib.sharding import fsdp_needed  # noqa: E402
from repro.launch.hlo_analysis import (Roofline, collective_bytes,  # noqa: E402
                                       loop_aware_cost)
from repro.launch.mesh import V5E, make_production_mesh, mesh_chips  # noqa: E402
from repro.models.lm.model import build_model  # noqa: E402
from repro.optim import get_optimizer  # noqa: E402
from repro.serve.engine import make_decode_step, make_prefill_step  # noqa: E402
from repro.train.step import make_train_step  # noqa: E402

"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell this prints/records:
  * memory_analysis()  — per-device argument/output/temp bytes (fits HBM?)
  * cost_analysis()    — per-device HLO FLOPs / bytes accessed
  * collective traffic — parsed from the post-SPMD HLO text
  * the three roofline terms (EXPERIMENTS.md §Roofline reads this JSON)

Usage::

    python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
    python -m repro.launch.dryrun --mesh both --out dryrun_results.json
    python -m repro.launch.dryrun --hier --arch grok-1-314b  # tiered sync
"""


def _tokens_per_step(cfg, shape) -> float:
    if shape.kind == "train":
        return shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return shape.global_batch * shape.seq_len
    return shape.global_batch * 1.0            # decode: one token


def _model_flops(cfg, shape, n_params_active: int) -> float:
    mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[shape.kind]
    return mult * n_params_active * _tokens_per_step(cfg, shape)


def _active_params(cfg, param_shapes) -> int:
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(param_shapes))
    if cfg.family == "moe" and cfg.moe is not None:
        expert = 0
        moe_leaves = param_shapes["layers"]["moe"]
        for name in ("w_gate", "w_up", "w_down"):
            expert += int(np.prod(moe_leaves[name].shape))
        total = total - expert + int(expert * cfg.moe.top_k
                                     / cfg.moe.n_experts)
    return total


def lower_cell(arch_id: str, shape_name: str, mesh, *,
               hier: bool = False, use_flash: Optional[bool] = None,
               microbatches: Optional[int] = None,
               remat_policy: Optional[str] = None,
               fsdp: Optional[bool] = None):
    """Lower one cell.  Returns (lowered, meta) — compile separately."""
    spec = get_arch(arch_id)
    shape = SHAPES[shape_name]
    cfg = spec.lm
    if use_flash is not None:
        cfg = cfg.variant(use_flash=use_flash)
    if remat_policy is not None:
        cfg = cfg.variant(remat_policy=remat_policy)
    # §Perf iteration 4: Megatron-SP residual only when the layer-scan's
    # saved residual stack would not fit; always for 32k prefill (no
    # gradient stacks, and the attention resharding replaces psums).
    mb = microbatches if microbatches is not None else spec.microbatches
    if shape.kind == "prefill":
        cfg = cfg.variant(seq_parallel=True)
    elif shape.kind == "train":
        dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                          if a in mesh.axis_names]))
        stack_gb = (cfg.n_layers * (shape.global_batch / dp / mb)
                    * shape.seq_len * cfg.d_model * 6) / 1e9
        if stack_gb > 4.0:
            cfg = cfg.variant(seq_parallel=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(model.init, key)
    total_params = sum(int(np.prod(s.shape))
                       for s in jax.tree.leaves(param_shapes))
    if fsdp is None:
        # §Perf iteration 1: FSDP only when TP-only state would not fit —
        # otherwise the per-microbatch weight re-gather dominates the
        # collective roofline term for nothing.
        opt_bpp = 4 if spec.optimizer == "sgdm" else 8
        fsdp = (shape.kind == "train" and
                fsdp_needed(mesh, total_params, opt_bpp))
    pshard = param_shardings(mesh, param_shapes, fsdp=fsdp)
    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
    repl = NamedSharding(mesh, P())

    meta: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name, "kind": shape.kind,
        "mesh": dict(mesh.shape), "hier": hier, "fsdp": fsdp,
        "seq_parallel": cfg.seq_parallel, "microbatches": mb,
        "active_params": _active_params(cfg, param_shapes),
        "total_params": total_params,
    }

    if shape.kind == "train":
        opt = get_optimizer(spec.optimizer)
        opt_shapes = jax.eval_shape(opt.init, param_shapes)
        state_shapes = {"params": param_shapes, "opt": opt_shapes}
        sshard = {"params": pshard,
                  "opt": opt_state_shardings(mesh, opt_shapes, fsdp=fsdp)}
        batch_struct = input_specs(cfg, shape)
        bshard = batch_shardings(mesh, batch_struct)
        tiers = None
        if hier:
            n_pods = mesh.shape.get("pod", 1)
            est_compute = (_model_flops(cfg, shape,
                                        meta["active_params"])
                           / (mesh_chips(mesh) * V5E.peak_flops * 0.4))
            tiers = choose_tiers(param_shapes, n_pods=n_pods,
                                 dcn_bytes_per_s=V5E.dcn_bw,
                                 compute_seconds=est_compute)
            meta["tiers"] = tiers.describe()
        step = make_train_step(model, opt, microbatches=mb,
                               hier_sync=hier, tiers=tiers)
        jitted = jax.jit(step, in_shardings=(sshard, bshard, repl),
                         out_shardings=(sshard, None),
                         donate_argnums=(0,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(state_shapes, batch_struct, key_struct)
        return lowered, meta

    if shape.kind == "prefill":
        step = make_prefill_step(model, max_len=shape.seq_len)
        batch_struct = input_specs(cfg, shape)
        bshard = batch_shardings(mesh, batch_struct)
        jitted = jax.jit(step, in_shardings=(pshard, bshard))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(param_shapes, batch_struct)
        return lowered, meta

    # decode: one new token against a seq_len cache
    B, S = shape.global_batch, shape.seq_len
    kw = {"enc_len": S} if cfg.family == "encdec" else {}
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, S, **kw))
    cshard = cache_shardings(mesh, cache_shapes, B)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tshard = batch_shardings(mesh, {"t": tok})["t"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(model)
    jitted = jax.jit(step, in_shardings=(pshard, tshard, cshard, repl),
                     out_shardings=(None, cshard), donate_argnums=(2,))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(param_shapes, tok, cache_shapes, pos)
    return lowered, meta


def analyse(lowered, meta, hw=V5E) -> Dict[str, Any]:
    t0 = time.perf_counter()
    compiled = lowered.compile()
    meta["compile_s"] = round(time.perf_counter() - t0, 1)
    chips = int(np.prod(list(meta["mesh"].values())))

    ma = compiled.memory_analysis()
    meta["memory"] = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        "peak_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 1e9,
        "fits_16gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes +
                      ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        < hw.hbm_bytes,
    }
    ca = compiled.cost_analysis()
    # XLA counts while bodies once; the loop-aware walk corrects by trip
    # count (both are recorded; the roofline uses the corrected numbers).
    meta["xla_cost"] = {"flops_per_dev": float(ca.get("flops", 0.0)),
                        "bytes_per_dev": float(ca.get("bytes accessed",
                                                      0.0))}
    hlo_text = compiled.as_text()
    flops_dev, bytes_dev, coll_dev = loop_aware_cost(hlo_text)

    stats = collective_bytes(hlo_text)
    meta["collectives"] = {"by_kind_gb": {k: v / 1e9 for k, v in
                                          stats.bytes_by_kind.items()},
                           "counts": stats.count_by_kind,
                           "static_total_gb": stats.total_bytes / 1e9,
                           "loop_aware_gb": coll_dev / 1e9}

    cfg = get_arch(meta["arch"]).lm
    shape = SHAPES[meta["shape"]]
    roof = Roofline(
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_bytes_per_device=coll_dev,
        chips=chips, peak_flops=hw.peak_flops, hbm_bw=hw.hbm_bw,
        link_bw=hw.ici_bw,
        model_flops=_model_flops(cfg, shape, meta["active_params"]))
    meta["roofline"] = {k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in roof.row().items()}
    return meta


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, *,
             hier: bool = False, **kw) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered, meta = lower_cell(arch_id, shape_name, mesh, hier=hier, **kw)
    return analyse(lowered, meta)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--hier", action="store_true",
                    help="use HierTrain tiered gradient sync (train cells)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--use-flash", action="store_true", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args()

    archs = sorted(ARCHS) if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results = []
    failures = 0
    for arch_id in archs:
        spec = get_arch(arch_id)
        shapes = (list(spec.shapes) + sorted(spec.skips)
                  if args.shape == "all" else args.shape.split(","))
        for shape_name in shapes:
            if shape_name in spec.skips:
                results.append({"arch": arch_id, "shape": shape_name,
                                "status": "SKIP",
                                "reason": spec.skips[shape_name]})
                print(f"[SKIP] {arch_id} x {shape_name}")
                continue
            for multi in meshes:
                tag = f"{arch_id} x {shape_name} x " \
                      f"{'2x16x16' if multi else '16x16'}" \
                      + (" [hier]" if args.hier else "")
                try:
                    t0 = time.perf_counter()
                    meta = run_cell(arch_id, shape_name, multi,
                                    hier=args.hier,
                                    use_flash=args.use_flash,
                                    microbatches=args.microbatches)
                    meta["status"] = "OK"
                    dt = time.perf_counter() - t0
                    r = meta["roofline"]
                    print(f"[OK]  {tag}: compile={meta['compile_s']}s "
                          f"peak={meta['memory']['peak_gb']:.2f}GB/dev "
                          f"dominant={r['dominant']} "
                          f"terms(c/m/n)={r['compute_s']:.4f}/"
                          f"{r['memory_s']:.4f}/{r['collective_s']:.4f}s "
                          f"useful={r['useful_ratio']:.2f} "
                          f"({dt:.0f}s)")
                    results.append(meta)
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc()
                    results.append({"arch": arch_id, "shape": shape_name,
                                    "multi_pod": multi, "status": "FAIL",
                                    "error": f"{type(e).__name__}: {e}"})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out} ({len(results)} cells)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
