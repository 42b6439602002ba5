"""One front door: ``Fleet`` → :func:`plan` → :class:`Plan`
(DESIGN.md §9).

HierTrain's value is one decision — where to cut layers and how to split
samples across an M-device/edge/cloud fleet (Algorithm 1).  This module
is the single entry point to that decision and everything downstream of
it:

    from repro.api import Fleet, plan

    fleet = Fleet.from_table2(model="lenet5")          # paper testbed
    p = plan(lenet5(), fleet, B=64)                    # Algorithm 1
    print(p.explain())                                 # cut/split/cost map
    p.simulate()                                       # DES validation
    step = p.step_fn(lr=0.05)                          # jitted hybrid SGD
    out = p.train(data, steps=100)                     # straggler-aware loop

The classic (device, edge, cloud) triple is exactly a :class:`Fleet` at
``M = 1``; a heterogeneous M-device star is the same call with ``m >= 2``
(or any custom :class:`Fleet`).  ``plan`` resolves to the topology-native
planner (solver, cost model, DES) — bit-for-bit identical across
topologies at M = 1 for the latency objective — and the returned
:class:`Plan` carries the chosen schedule, the predicted
``t_total``/``t_period``, and executable methods.  Execution has no
topology fork: every fleet runs the one hybrid step (DESIGN.md §3).

Every pre-facade entry point (``solve``/``solve_multi``, ``t_total*``,
``simulate_iteration*``, ``run_*_hier_loop``) survives as a thin
deprecation shim over this module and returns bit-identical results
(``tests/test_api.py`` asserts it).

CLI smoke: ``python -m repro.api --explain lenet5 [--m 2] [--batch 64]``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Union

import numpy as np

from repro import obs
from repro.core import pipeline as _pipeline
from repro.core import scheduler as _scheduler
from repro.core import simulator as _simulator
from repro.core.cost_model import (Breakdown, MultiSchedule, Schedule,
                                   _t_total_multi)
from repro.core.fleet import STAR, TREE, TRIPLE, Fleet
from repro.core.layerstack import LayerStack, as_layerstack

__all__ = ["Fleet", "Plan", "plan", "as_layerstack"]

OBJECTIVES = _scheduler.OBJECTIVES


@dataclasses.dataclass
class Plan:
    """The resolved HierTrain decision for one (model, fleet, B) triple.

    ``schedule`` is the topology-native object (a ``Schedule`` on the
    classic triple, a ``MultiSchedule`` on a star) — use
    :attr:`multi_schedule` for the unified view.  ``result`` is the full
    native scheduler result (LP/prune counters, search log).
    """
    fleet: Fleet
    B: int
    objective: str
    pipeline_depth: int
    backend: str
    profile: Any                  # HierProfile | MultiProfile (native;
    #                               wire-compressed MO/MG when wire != none)
    network: Any                  # Network | StarNetwork (native)
    result: Any                   # SchedulerResult | MultiSchedulerResult
    wire: str = "none"            # cut-point transfer codec (core/wire.py)
    model: Optional[LayerStack] = None

    # ---- the decision ---------------------------------------------------

    @property
    def schedule(self) -> Union[Schedule, MultiSchedule]:
        return self.result.schedule

    @property
    def multi_schedule(self) -> MultiSchedule:
        """The schedule in the unified M-device representation."""
        s = self.schedule
        return s if isinstance(s, MultiSchedule) \
            else MultiSchedule.from_schedule(s)

    @property
    def breakdown(self) -> Breakdown:
        """Exact per-phase Eq.-12 latencies of the chosen schedule."""
        return self.result.breakdown

    @property
    def t_total(self) -> float:
        """Predicted single-iteration (barrier) latency, seconds."""
        return self.result.t_total

    @property
    def t_period(self) -> float:
        """Predicted pipelined steady-state period (DESIGN.md §7)."""
        return self.result.t_period

    def pipeline_time(self, K: Optional[int] = None) -> float:
        """Model wall-clock of a depth-K pipelined run:
        ``T(K) = T_fill + (K - 1) * T_period``.  ``K`` defaults to the
        plan's ``pipeline_depth``."""
        K = self.pipeline_depth if K is None else K
        return _pipeline.t_pipeline(self.profile, self.network,
                                    self.schedule, K)

    # ---- validation -----------------------------------------------------

    def simulate(self, K: int = 1) -> float:
        """Discrete-event-simulated makespan of ``K`` pipelined
        iterations (``K = 1``: one barrier iteration).  Runs the
        topology-native DES, so triple fleets reproduce the paper's
        three-worker simulation exactly."""
        if K == 1:
            if self.fleet.topology == TRIPLE:
                return _simulator._simulate_iteration(
                    self.profile, self.network, self.schedule)
            return _simulator._simulate_iteration_multi(
                self.profile, self.network, self.schedule)
        return _simulator.simulate_pipeline(self.profile, self.network,
                                            self.schedule, K)

    def baseline(self, tier: str) -> float:
        """Exact ``T_total`` of the all-on-one-worker baseline schedule
        (``tier`` in ``"device" | "edge" | "cloud"``) on this fleet's
        cost model — the paper's All-Edge/All-Cloud comparison points."""
        if tier not in ("device", "edge", "cloud"):
            raise ValueError(f"unknown baseline tier: {tier!r} "
                             f"(pick 'device', 'edge' or 'cloud')")
        if self.fleet.topology == TRIPLE:
            from repro.core.baselines import all_on_one
            return all_on_one(self.profile, self.network, self.B,
                              tier).t_total
        prof = self.profile
        names = prof.worker_names
        S = prof.num_streams
        wo = tier if tier in ("edge", "cloud") else names[0]
        if wo == "edge" and wo not in names:    # tree: edge_0.. at E >= 2
            wo = names[prof.num_devices]
        rest = [w for w in names if w != wo]
        sched = MultiSchedule(worker_o=wo, worker_l=rest[-1],
                              s_workers=tuple(rest[:-1]), m_s=(0,) * S,
                              m_l=0, b_o=self.B, b_s=(0,) * S, b_l=0)
        return _t_total_multi(prof, self.network, sched).total

    # ---- execution ------------------------------------------------------

    def _require_model(self) -> LayerStack:
        if self.model is None:
            raise ValueError(
                "this Plan was built without a model (profile-only "
                "fleet); pass a model/LayerStack to plan() to execute")
        return self.model

    def stream_edges(self) -> tuple:
        """Per-TASK-S-stream hosting edge: on a tree a device stream sits
        under its radio's edge, an edge's own stream under itself, and a
        cloud-hosted stream merges with the front group (index 0); off
        the tree every stream is on edge 0."""
        ms = self.multi_schedule
        if self.fleet.topology != TREE:
            return (0,) * len(ms.m_s)
        from repro.core.hybrid_step import tree_stream_edges
        return tree_stream_edges(self.profile, self.network, ms)

    def step_fn(self, lr: float = 0.05, cloud_mesh=None) -> Callable:
        """A compiled ``(params, x, y) -> (new_params, loss)`` hybrid-SGD
        step for the chosen schedule (exact batch-B SGD semantics;
        ``params`` donated, executables cached per cut tuple).  Every
        fleet runs the one step (``hybrid_step.hybrid_sgd_step``) on
        :attr:`multi_schedule` and :meth:`stream_edges`.

        ``cloud_mesh`` (tree fleets only) runs the cloud tail segment
        data-parallel over the mesh's dp axes via ``shard_map``
        (DESIGN.md §12); the batch must divide by the dp shard count.

        Each call is the host span ``hiertrain.step`` (with its step
        number) around ``hiertrain.split_batch`` and
        ``hiertrain.dispatch``; a call with a new batch shape notes the
        program it dispatches (``repro.obs.note_step``), whose compiled
        text ``repro.obs.step_text`` reads."""
        import itertools

        import jax.numpy as jnp

        from repro.core import hybrid_step as hs

        stack = self._require_model()
        if cloud_mesh is not None and self.fleet.topology != TREE:
            raise ValueError("cloud_mesh is a tree-topology option; this "
                             f"plan's fleet is {self.fleet.topology!r}")
        ms = self.multi_schedule
        fn = hs.jitted_hybrid_step(stack, ms.m_s, ms.m_l, lr, wire=self.wire,
                                   stream_edge=self.stream_edges(),
                                   cloud_mesh=cloud_mesh)
        numbers = itertools.count()
        noted = []

        def step(params, x, y):
            with obs.step_span(next(numbers)):
                with obs.span("split_batch"):
                    b = hs.split_batch(jnp.asarray(x), jnp.asarray(y), ms)
                if noted != [np.shape(x)]:
                    obs.note_step(fn, (params, b), np.shape(x))
                    noted[:] = [np.shape(x)]
                with obs.span("dispatch"):
                    return fn(params, b)

        return step

    def init_params(self, key) -> Any:
        """Consensus initial weights (one pytree per cut-point)."""
        return self._require_model().init(key)

    def train(self, data, steps: int, lr: float = 0.05,
              resched_every: int = 20, ema: float = 0.3, seed: int = 0,
              worker_slowdown: Optional[Callable[[int], Dict[str, float]]]
              = None,
              log: Optional[Callable[[str], None]] = None, *,
              churn=None, ckpt_dir: Optional[str] = None,
              ckpt_every: int = 50, keep: int = 3,
              fail_at: Optional[int] = None) -> Dict[str, Any]:
        """Straggler-aware HierTrain loop: real hybrid JAX steps for the
        numerics, the calibrated cost model for the wall clock, online
        EMA re-profiling + re-scheduling every ``resched_every`` steps,
        and pipelined fill+period accounting when the plan was built with
        ``pipeline_depth > 1``.  Returns ``{params, history, wall,
        final_schedule, resumed_from, churn_log}``.

        ``churn`` — a :class:`repro.core.churn.ChurnTrace` of membership
        events for elastic star fleets (DESIGN.md §10); raises
        ``NotImplementedError`` naming the topology on any other fleet.  ``ckpt_dir``/``ckpt_every``/``keep``
        enable atomic keep-N checkpointing and crash-safe resume: rerun
        the same call after a crash and the loop restores the newest
        checkpoint and continues, bitwise equal to an uninterrupted run.
        ``fail_at`` injects a failure after that step (testing).  All
        four default off — the loop is then bit-identical to its
        pre-elastic behaviour."""
        from repro.train.loop import HierLoopConfig, _run_loop
        if churn is not None and self.fleet.topology != STAR:
            raise NotImplementedError(
                "churn (elastic membership) is only implemented for the "
                f"star topology; this plan's fleet is "
                f"topology={self.fleet.topology!r}")
        cfg = HierLoopConfig(
            total_steps=steps, batch=self.B, lr=lr,
            resched_every=resched_every, ema=ema, seed=seed,
            pipeline_depth=self.pipeline_depth, objective=self.objective,
            wire=self.wire, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
            keep=keep, fail_at=fail_at)
        return _run_loop(cfg, self._require_model(), self.profile,
                         self.network, data, worker_slowdown, log,
                         topology=self.fleet.topology,
                         initial_schedule=self.schedule, churn=churn)

    # ---- reporting ------------------------------------------------------

    def explain(self) -> str:
        """Human-readable cut/split/cost breakdown of the decision."""
        bd = self.breakdown
        s = self.schedule
        res = self.result
        name = self.model.name if self.model is not None else "(profile)"
        ms = s.m_s if isinstance(s.m_s, int) else \
            "/".join(str(m) for m in s.m_s)
        t_edge, t_cloud = self.baseline("edge"), self.baseline("cloud")
        lines = [
            f"HierTrain plan — model={name}  fleet[{self.fleet.describe()}]",
            f"  batch B={self.B}  objective={self.objective}  "
            f"backend={self.backend}  wire={self.wire}",
            f"  schedule: {s.describe()}",
            f"  cuts: m_s={ms}  m_l={s.m_l}  of N={self.profile.num_layers}"
            f" layers",
            f"  predicted: T_total={bd.total:.6g}s  "
            f"T_period={self.t_period:.6g}s",
            f"  phases (s): f1={bd.t_f1:.4g} b1={bd.t_b1:.4g} "
            f"f2={bd.t_f2:.4g} b2={bd.t_b2:.4g} f3={bd.t_f3:.4g} "
            f"b3={bd.t_b3:.4g} update={bd.t_update:.4g}",
            f"  comm (s): input={bd.comm_input:.4g} "
            f"activation={bd.comm_activation:.4g} "
            f"weight-sync={bd.comm_weightgrad:.4g}",
            f"  baselines: all-edge={t_edge:.6g}s "
            f"({t_edge / bd.total:.2f}x)  all-cloud={t_cloud:.6g}s "
            f"({t_cloud / bd.total:.2f}x)",
        ]
        if self.pipeline_depth > 1:
            K = self.pipeline_depth
            tk = self.pipeline_time(K)
            lines.append(
                f"  pipelined: T(K={K})={tk:.6g}s vs barrier "
                f"{K * bd.total:.6g}s ({K * bd.total / tk:.2f}x)")
        search = (f"  search: {res.n_candidates} candidates, "
                  f"{res.n_pruned} pruned, {res.n_lp_solved} LPs")
        if getattr(res, "n_lp_refine", 0):
            search += (f" (+{res.n_lp_refine} refine LPs, "
                       f"{res.refine_rounds} rounds)")
        lines.append(search)
        return "\n".join(lines)


def _prepare(model, fleet: Fleet, wire: Optional[str]):
    """Shared plan-request prep: resolve the wire codec, adapt the model
    to a :class:`LayerStack`, build the wire-adjusted profile and the
    native network.  Used by :func:`plan` and by the cross-fleet planner
    (``repro.serve.planner``), so both see identical solver inputs."""
    from repro.core.wire import apply_wire, validate_wire
    wire = fleet.wire if wire is None else validate_wire(wire)
    stack = as_layerstack(model) if model is not None else None
    profile = apply_wire(fleet.profile_for(stack), stack, wire)
    net = fleet.network()
    return stack, profile, net, wire


def plan_many(requests, **kwargs):
    """Batch front door: plan many fleets in shared tableau stacks with a
    fingerprinted plan cache (``repro.serve.planner``, DESIGN.md §13).
    Takes :class:`repro.serve.planner.PlanRequest` items (or anything the
    planner coerces); returns plans in request order."""
    from repro.serve import planner as _planner
    return _planner.plan_many(requests, **kwargs)


def plan(model, fleet: Fleet, B: int, *, objective: str = "latency",
         pipeline_depth: int = 1, backend: str = "batched",
         wire: Optional[str] = None,
         prune: bool = True, refine_passes: int = 4,
         keep_log: bool = False,
         warm_start: Optional[Union[Schedule, MultiSchedule]] = None
         ) -> Plan:
    """Solve Algorithm 1 for ``(model, fleet, B)`` and return a
    :class:`Plan`.

    ``model`` is anything :func:`repro.core.layerstack.as_layerstack`
    accepts (a layered CNN, an LM model-zoo adapter, any ``LayerStack``),
    or ``None`` for pinned-profile fleets used purely for scheduling.
    ``objective`` is ``"latency"`` (Eq.-12 ``T_total``) or
    ``"throughput"`` (steady-state period, DESIGN.md §7);
    ``pipeline_depth`` records how many minibatches ``Plan.train`` keeps
    in flight.  ``backend``/``prune``/``refine_passes``/``keep_log`` are
    forwarded to the topology-native engine.  ``warm_start`` (a feasible
    topology-native schedule, e.g. the live one before a fleet change)
    tightens the dominance prune without changing the result
    (DESIGN.md §10).

    ``wire`` selects the cut-point transfer codec (DESIGN.md §11):
    ``None`` inherits ``fleet.wire``; ``"int8"`` both *plans with* the
    compressed ``MO``/``MG`` wire sizes (so Algorithm 1 sees the
    compressed split-point traffic — optimal cuts legitimately move)
    and *executes* the matching quantize→dequantize codec in
    :meth:`Plan.step_fn` / :meth:`Plan.train`.
    """
    if pipeline_depth < 1:
        raise ValueError("pipeline_depth must be >= 1")
    stack, profile, net, wire = _prepare(model, fleet, wire)
    if fleet.topology == TRIPLE:
        result = _scheduler._solve_3w(
            profile, net, B, keep_log=keep_log, backend=backend,
            prune=prune, objective=objective, warm_start=warm_start)
    else:
        result = _scheduler._solve_multi(
            profile, net, B, keep_log=keep_log, backend=backend,
            prune=prune, refine_passes=refine_passes, objective=objective,
            warm_start=warm_start)
    return Plan(fleet=fleet, B=B, objective=objective,
                pipeline_depth=pipeline_depth, backend=backend,
                profile=profile, network=net, result=result, wire=wire,
                model=stack)


# ---------------------------------------------------------------------------
# CLI: python -m repro.api --explain <config>
# ---------------------------------------------------------------------------

_CLI_CONFIGS = ("lenet5", "alexnet", "lm")


def _cli_model_and_fleet(config: str, m: int, edge_cloud_mbps, topology,
                         n_edges: int = 1):
    if config in ("lenet5", "alexnet"):
        from repro.models import cnn
        model = getattr(cnn, config)()
        return model, Fleet.from_table2(
            model=config, m=m,
            edge_cloud_mbps=3.0 if edge_cloud_mbps is None
            else edge_cloud_mbps,
            topology=topology, n_edges=n_edges)
    if config == "lm":
        if topology == TRIPLE:
            raise SystemExit("the lm fleet is star-native; drop "
                             "--topology triple")
        from repro.core.fleet import LM_BACKHAUL_MBPS
        from repro.models.lm.layerstack import lm_layerstack
        from repro.models.lm.model import LMConfig
        cfg = LMConfig(name="api-lm", family="dense", n_layers=6,
                       d_model=256, n_heads=4, n_kv_heads=2, d_ff=768,
                       vocab=32_000)
        fleet = Fleet.lm_default(
            m=m, backhaul_mbps=LM_BACKHAUL_MBPS if edge_cloud_mbps is None
            else edge_cloud_mbps)
        return lm_layerstack(cfg, seq_len=256), fleet
    raise SystemExit(f"unknown config {config!r}; pick one of "
                     f"{_CLI_CONFIGS}")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.api",
        description="Plan a HierTrain schedule and explain it.")
    ap.add_argument("--explain", metavar="CONFIG", required=True,
                    help=f"one of {', '.join(_CLI_CONFIGS)}")
    ap.add_argument("--m", type=int, default=1,
                    help="number of devices in the fleet")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--edge-cloud-mbps", type=float, default=None,
                    help="edge-cloud backhaul (default: 3 Mbps for the "
                         "CNN testbeds, 200 Mbps for the lm fleet)")
    ap.add_argument("--objective", choices=OBJECTIVES, default="latency")
    ap.add_argument("--pipeline-depth", type=int, default=1)
    ap.add_argument("--topology", choices=("auto", TRIPLE, STAR, TREE),
                    default="auto")
    ap.add_argument("--edges", type=int, default=1,
                    help="edge-server count (tree topology; devices are "
                         "partitioned contiguously)")
    ap.add_argument("--wire", choices=("none", "int8"), default="none",
                    help="cut-point transfer codec: int8 plans with and "
                         "executes compressed activation/gradient wires")
    args = ap.parse_args(argv)
    model, fleet = _cli_model_and_fleet(args.explain, args.m,
                                        args.edge_cloud_mbps, args.topology,
                                        n_edges=args.edges)
    p = plan(model, fleet, args.batch, objective=args.objective,
             pipeline_depth=args.pipeline_depth, wire=args.wire)
    print(p.explain())
    print(f"  simulated (DES): {p.simulate():.6g}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
