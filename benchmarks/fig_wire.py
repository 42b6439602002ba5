"""Wire-compression benchmark (beyond the paper — DESIGN.md §11).

Two halves, mirroring :mod:`repro.core.wire` itself:

* **Planning (deterministic, drift-checked)** — for the attention and
  gla LM fleets of :mod:`benchmarks.fig_lm_fleet` at M in {1, 2, 4},
  plan the same workload with ``wire="none"`` and ``wire="int8"`` and
  record how the latency-optimal schedule moves.  An int8 wire shrinks
  the forward (bf16) crossing ~2x and the backward (f32) crossing ~4x,
  so split-point traffic stops dominating and the optimizer pushes the
  cuts deeper / rebalances the batch — the arXiv:2403.15815 effect, now
  visible to Algorithm 1 because ``apply_wire`` rewrites the ``MO``/
  ``MG`` columns every LP reads.

* **Execution (timed, not drift-checked)** — step-time of a tiny
  executable zamba stack (both Pallas kernels on its path) under
  wire x backend, on a fixed offloading schedule.  On CPU CI the Pallas
  path runs in interpret mode, so these timings are shape checks, not
  speedups; the accelerator story is the roofline report's job.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmarks.common import table
from benchmarks.fig_lm_fleet import BATCH, CONFIGS, M_SWEEP, SEQ_LEN
from repro.api import Fleet, plan
from repro.core.cost_model import MultiSchedule, Schedule
from repro.core.hybrid_step import jitted_hybrid_step, split_batch
from repro.models.lm.layerstack import lm_layerstack
from repro.models.lm.model import LMConfig
from repro.models.lm.ssm import SSMConfig

FAMILIES = ("attention", "gla")

# Executable stack for the step-time half: zamba so one model exercises
# both kernels (mamba2 -> gla_scan, shared attn -> flash_attention).
EXEC_CFG = LMConfig(
    name="wire-exec", family="zamba", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=128, vocab=512,
    ssm=SSMConfig(d_state=16, head_dim=16, expand=2, chunk=16),
    shared_attn_every=1, dtype=jnp.float32)
EXEC_SEQ = 32
EXEC_BATCH = 18
EXEC_STEPS = 3


def _cuts(sched) -> tuple:
    return (tuple(sched.m_s), sched.m_l)


def _rows() -> List[Dict]:
    rows: List[Dict] = []
    for family in FAMILIES:
        stack = lm_layerstack(CONFIGS[family], seq_len=SEQ_LEN)
        for m in M_SWEEP:
            fleet = Fleet.lm_default(m=m)
            p0 = plan(stack, fleet, BATCH, objective="latency")
            p1 = plan(stack, fleet, BATCH, objective="latency",
                      wire="int8")
            rows.append({
                "family": family, "M": m,
                "layers": p0.profile.num_layers,
                "t_total_none": p0.t_total,
                "t_total_int8": p1.t_total,
                "wire_gain": p0.t_total / p1.t_total,
                # embed-cut compression ratios (bf16 fwd / f32 bwd)
                "mo_ratio": float(p1.profile.MO[0] / p0.profile.MO[0]),
                "mg_ratio": float(p1.profile.MG[0] / p0.profile.MG[0]),
                "cut_shifted": _cuts(p1.schedule) != _cuts(p0.schedule),
                "schedule_none": p0.schedule.describe(),
                "schedule_int8": p1.schedule.describe(),
            })
    return rows


def _exec_rows() -> List[Dict]:
    sched = MultiSchedule.from_schedule(Schedule(
        worker_o="edge", worker_s="device", worker_l="cloud",
        m_s=2, m_l=4, b_o=6, b_s=6, b_l=6))
    key = jax.random.PRNGKey(0)
    rows: List[Dict] = []
    for backend in ("ref", "pallas"):
        stack = lm_layerstack(EXEC_CFG, seq_len=EXEC_SEQ, backend=backend)
        x, y = stack.dummy_batch(jax.random.fold_in(key, 1), EXEC_BATCH)
        batches = split_batch(x, y, sched)
        for wire in ("none", "int8"):
            step = jitted_hybrid_step(stack, sched.m_s, sched.m_l, 0.05,
                                      wire=wire)
            params = stack.init(key)
            params, loss = step(params, batches)      # compile + warm
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(EXEC_STEPS):
                params, loss = step(params, batches)
            jax.block_until_ready(loss)
            rows.append({
                "backend": backend, "wire": wire,
                "step_ms": (time.perf_counter() - t0) / EXEC_STEPS * 1e3,
                "final_loss": float(loss),
            })
    return rows


def run() -> str:
    rows = _rows()
    out = [table(rows, ("family", "M", "layers", "t_total_none",
                        "t_total_int8", "wire_gain", "mo_ratio",
                        "mg_ratio", "cut_shifted"),
                 title=f"Wire compression: int8 cut-point transfers "
                       f"(T={SEQ_LEN}, B={BATCH})")]
    for r in rows:
        out.append(f"  {r['family']:>9} M={r['M']}: "
                   f"none [{r['schedule_none']}]")
        out.append(f"  {'':>9}      int8 [{r['schedule_int8']}]")
    ex = _exec_rows()
    out.append(table(ex, ("backend", "wire", "step_ms", "final_loss"),
                     title=f"Executable zamba step (T={EXEC_SEQ}, "
                           f"B={EXEC_BATCH}; CPU interpret mode — "
                           f"shape check, not a speedup claim)"))
    return "\n".join(out)


def run_json(include_exec: bool = True) -> Dict[str, List[Dict]]:
    payload: Dict[str, List[Dict]] = {"rows": _rows()}
    if include_exec:
        payload["exec"] = _exec_rows()
    return payload


if __name__ == "__main__":
    print(run())
