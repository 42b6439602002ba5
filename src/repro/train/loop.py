"""Fault-tolerant training loop.

Failure model and mitigations (single-host container, 1000-node
protocol):

* **Checkpoint/restart** — atomic keep-N checkpoints every
  ``ckpt_every`` steps; on start the loop restores the latest and
  resumes at the recorded step.
* **Deterministic skip-ahead** — the data pipeline is stateless
  (batch k is pure in (seed, k)), so resume needs no pipeline replay.
* **Failure injection** — ``fail_at`` raises mid-run (after the
  gradient step, before the checkpoint) to exercise the recovery path;
  the integration test restarts the loop and asserts bit-identical
  convergence with an uninterrupted run.
* **Straggler mitigation** (HierTrain-native) — for the hierarchical
  CNN trainer, measured per-step worker times feed an EMA profile and
  the Algorithm-1 scheduler re-solves every ``resched_every`` steps:
  a slowed worker automatically sheds samples/layers.  This is the
  paper's profiling stage run *online*.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint.store import CheckpointManager

Tree = Any


class InjectedFailure(RuntimeError):
    pass


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep: int = 3
    log_every: int = 10
    fail_at: Optional[int] = None     # raise after completing this step
    seed: int = 0


def run_train_loop(cfg: LoopConfig, state: Tree, train_step: Callable,
                   batch_fn: Callable[[int], Tree],
                   shardings: Optional[Tree] = None,
                   log: Optional[Callable[[str], None]] = print
                   ) -> Dict[str, Any]:
    """Run (or resume) training.  Returns {state, history, resumed_from}."""
    manager = CheckpointManager(cfg.ckpt_dir, cfg.keep) if cfg.ckpt_dir \
        else None
    start = 0
    resumed_from = None
    if manager is not None:
        step, restored = manager.restore_latest(state, shardings)
        if restored is not None:
            state, start, resumed_from = restored, step, step

    key = jax.random.PRNGKey(cfg.seed)
    history: List[Dict[str, float]] = []
    t_last = time.perf_counter()
    for step in range(start, cfg.total_steps):
        batch = jax.tree.map(jax.numpy.asarray, batch_fn(step))
        state, metrics = train_step(state, batch,
                                    jax.random.fold_in(key, step))
        if cfg.log_every and (step + 1) % cfg.log_every == 0:
            m = {k: float(v) for k, v in metrics.items()}
            now = time.perf_counter()
            m["steps_per_s"] = cfg.log_every / (now - t_last)
            t_last = now
            m["at"] = step + 1
            history.append(m)
            if log:
                log(f"step {step+1}: loss={m['loss']:.4f} "
                    f"gnorm={m.get('grad_norm', float('nan')):.3f} "
                    f"({m['steps_per_s']:.2f} it/s)")
        if manager is not None and (step + 1) % cfg.ckpt_every == 0:
            manager.save(step + 1, state, extra={"seed": cfg.seed})
        if cfg.fail_at is not None and step + 1 == cfg.fail_at:
            raise InjectedFailure(f"injected failure after step {step+1}")
    return {"state": state, "history": history,
            "resumed_from": resumed_from}


# ---------------------------------------------------------------------------
# Hierarchical (mobile-edge-cloud) CNN training with online re-scheduling
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class HierLoopConfig:
    total_steps: int
    batch: int
    lr: float = 0.05
    resched_every: int = 20           # straggler mitigation cadence
    ema: float = 0.3
    seed: int = 0
    pipeline_depth: int = 1           # K minibatches in flight (§7); 1 =
    #                                   barrier-per-iteration execution
    objective: str = "latency"        # scheduler objective (§7)
    wire: str = "none"                # cut-point transfer codec (§11);
    #                                   the caller's profile must carry
    #                                   matching (compressed) MO/MG
    ckpt_dir: Optional[str] = None    # crash-safe resume (DESIGN.md §10)
    ckpt_every: int = 50
    keep: int = 3
    fail_at: Optional[int] = None     # raise after completing this step


def _sched_to_json(s) -> Dict[str, Any]:
    """JSON form of a (Multi)Schedule — ints and strings only, so the
    round-trip through the checkpoint manifest is exact."""
    from repro.core.cost_model import MultiSchedule
    if isinstance(s, MultiSchedule):
        return {"kind": "star", "worker_o": s.worker_o,
                "worker_l": s.worker_l, "s_workers": list(s.s_workers),
                "m_s": list(s.m_s), "m_l": s.m_l, "b_o": s.b_o,
                "b_s": list(s.b_s), "b_l": s.b_l}
    return {"kind": "triple", "worker_o": s.worker_o,
            "worker_s": s.worker_s, "worker_l": s.worker_l, "m_s": s.m_s,
            "m_l": s.m_l, "b_o": s.b_o, "b_s": s.b_s, "b_l": s.b_l}


def _sched_from_json(d: Dict[str, Any]):
    from repro.core.cost_model import MultiSchedule, Schedule
    if d["kind"] == "star":
        return MultiSchedule(
            worker_o=d["worker_o"], worker_l=d["worker_l"],
            s_workers=tuple(d["s_workers"]), m_s=tuple(d["m_s"]),
            m_l=d["m_l"], b_o=d["b_o"], b_s=tuple(d["b_s"]), b_l=d["b_l"])
    return Schedule(d["worker_o"], d["worker_s"], d["worker_l"], d["m_s"],
                    d["m_l"], d["b_o"], d["b_s"], d["b_l"])


def _prof_arrays(p) -> Dict[str, np.ndarray]:
    return {"L_f": np.asarray(p.L_f), "L_b": np.asarray(p.L_b),
            "L_u": np.asarray(p.L_u)}


def _profile_from_arrays(template, worker_names, arrays):
    """Rebuild a profile from checkpointed timing rows.  The per-layer
    columns (MP/MO/MG/sample_bytes) are hardware-membership invariant, so
    they come from the caller's template; the per-worker rows and (for a
    star) the membership come from the checkpoint."""
    from repro.core.cost_model import (HierProfile, MultiProfile,
                                       TreeProfile)
    if worker_names is None:
        return HierProfile(
            layer_names=template.layer_names, L_f=arrays["L_f"],
            L_b=arrays["L_b"], L_u=arrays["L_u"], MP=template.MP,
            MO=template.MO, sample_bytes=template.sample_bytes,
            MG=template.MG)
    common = dict(
        layer_names=template.layer_names, worker_names=tuple(worker_names),
        L_f=arrays["L_f"], L_b=arrays["L_b"], L_u=arrays["L_u"],
        MP=template.MP, MO=template.MO,
        sample_bytes=template.sample_bytes, MG=template.MG)
    if isinstance(template, TreeProfile):
        return TreeProfile(n_edges=template.n_edges,
                           cloud_speedup=template.cloud_speedup, **common)
    return MultiProfile(**common)


def _ema_profile_update(prof, baseline, slow: Dict[str, float],
                        worker_names, ema: float) -> None:
    """EMA every worker toward its *currently observed* speed.

    Workers absent from ``slow`` decay toward the baseline profile
    (factor 1.0) — this is what lets a healed straggler recover: the old
    code only touched workers the monitor still reported, so a worker
    that stopped straggling kept its degraded profile forever.
    """
    for i, w in enumerate(worker_names):
        factor = slow.get(w, 1.0)
        for name in ("L_f", "L_b", "L_u"):
            cur = getattr(prof, name)
            target = getattr(baseline, name)[i] * factor
            cur[i] = (1 - ema) * cur[i] + ema * target
    if hasattr(prof, "_prefix"):
        del prof._prefix


def _loop_ops(topology: str, model, profile, net, cfg: "HierLoopConfig"):
    """Topology-native function bundle for :func:`_run_loop`.

    The planner side (solver, fill/period cost model, worker names) is
    topology-native; the step is not: every topology runs the one hybrid
    step (DESIGN.md §3), the triple as its M=1 star.  History formats are
    preserved per topology: the triple records scalar ``m_s`` and a
    3-tuple ``b``, the star and tree record the ``m_s`` tuple and an
    (M+2)-tuple ``b``.
    """
    from repro.core import scheduler
    from repro.core.cost_model import MultiSchedule
    from repro.core.hybrid_step import (jitted_hybrid_step, split_batch,
                                        tree_stream_edges)

    if topology == "triple":
        from repro.core.cost_model import WORKERS, _t_total
        from repro.core.pipeline import t_period

        ops = dict(
            names=WORKERS,
            widx={w: i for i, w in enumerate(WORKERS)},
            solve=lambda p, warm=None: scheduler._solve_3w(
                p, net, cfg.batch, objective=cfg.objective,
                warm_start=warm),
            fill=lambda p, s: _t_total(p, net, s).total,
            period=lambda p, s: t_period(p, net, s),
            hist=lambda s: {"m_s": s.m_s, "m_l": s.m_l,
                            "b": (s.b_o, s.b_s, s.b_l)},
            tag="hier",
        )
    else:
        assert topology in ("star", "tree"), topology
        # Straggler EMAs are already per-edge on a tree: every edge
        # server is its own row of ``worker_names``.
        from repro.core.cost_model import _t_total_multi
        from repro.core.pipeline import t_period_multi

        ops = dict(
            names=profile.worker_names,
            widx=profile.widx,
            solve=lambda p, warm=None: scheduler._solve_multi(
                p, net, cfg.batch, objective=cfg.objective,
                warm_start=warm),
            fill=lambda p, s: _t_total_multi(p, net, s).total,
            period=lambda p, s: t_period_multi(p, net, s),
            hist=lambda s: {"m_s": s.m_s, "m_l": s.m_l,
                            "b": (s.b_o, *s.b_s, s.b_l)},
            tag="tree-hier" if topology == "tree" else "multi-hier",
        )

    def step(params, x, y, s):
        """One hybrid step on schedule ``s``.  On a tree each edge
        pre-merges its same-cut streams; the stream→edge map depends on
        the live schedule, so it is re-derived per call."""
        ms = s if isinstance(s, MultiSchedule) \
            else MultiSchedule.from_schedule(s)
        edges = tree_stream_edges(profile, net, ms) if topology == "tree" \
            else None
        fn = jitted_hybrid_step(model, ms.m_s, ms.m_l, cfg.lr,
                                wire=cfg.wire, stream_edge=edges)
        return fn(params, split_batch(x, y, ms))

    ops["step"] = step
    return ops


def _run_loop(cfg: HierLoopConfig, model, profile, net, data,
              worker_slowdown: Optional[Callable[[int], Dict[str, float]]]
              = None, log: Optional[Callable[[str], None]] = None, *,
              topology: str, initial_schedule=None,
              churn=None) -> Dict[str, Any]:
    """Train any layer stack under the HierTrain schedule, re-solving the
    schedule online as (simulated) worker speeds drift — the engine
    behind :meth:`repro.api.Plan.train` for both topologies.

    ``model`` is anything :func:`repro.core.layerstack.as_layerstack`
    accepts — a layered CNN or an LM model-zoo adapter
    (:mod:`repro.models.lm.layerstack`); ``data.batch(step)`` must return
    ``{"x", "labels"}`` arrays whose leading axis is the sample axis.

    ``worker_slowdown(step)`` returns per-worker-name slowdown factors —
    the straggler injection used by tests/benchmarks.  Execution is
    simulated with the calibrated cost model for timing and with the
    *real* hybrid JAX step for the numerics.

    Re-scheduling is gated on cadence alone (every ``resched_every``
    steps): each tick EMAs *every* worker toward its observed speed — so
    a straggler that heals decays back to the baseline profile and the
    loop returns to the pre-straggle schedule.

    With ``cfg.pipeline_depth = K > 1`` the wall clock models pipelined
    steady-state execution (DESIGN.md §7): the first step of each
    K-window pays the Eq.-12 fill latency and the remaining ``K - 1``
    pay one ``t_period`` each — and a re-schedule that actually changes
    the schedule breaks the pipe, so the fill is re-paid at that step
    regardless of window position.

    **Elastic fleets** (DESIGN.md §10): ``churn`` is a
    :class:`~repro.core.churn.ChurnTrace` (star topology only).  Events
    pinned to step ``s`` are applied at the top of step ``s``; a
    membership change remaps the live schedule onto the survivors and
    re-solves with it as a warm incumbent (bit-identical to a cold
    solve on the survivor fleet, by the ``_warm_ok`` certificate), a
    crash additionally charges the lost in-flight fill as recovery
    time, and a join seeds the newcomer's profile rows from the fleet's
    reference tier for the EMA to refine.  Measured solver seconds land
    only in the returned ``churn_log`` — the simulated ``wall`` stays a
    pure function of (cost model, trace, seed) so resume is
    bit-reproducible.

    **Crash-safe resume**: with ``cfg.ckpt_dir`` set, every
    ``cfg.ckpt_every`` steps the loop atomically checkpoints params,
    the EMA'd and baseline profiles, the reference rows, the schedule,
    the network, the simulated wall clock, and the step.  On start the
    loop restores the newest readable checkpoint and continues; a
    resumed run is bitwise equal to an uninterrupted one from the
    resume step onward (``history`` then covers only the resumed tail;
    ``resumed_from`` records the step).  ``cfg.fail_at`` injects a
    failure after that step completes (post-checkpoint) to exercise
    the path.
    """
    import copy

    if churn is not None and topology != "star":
        raise ValueError(
            "churn is native to the star topology: membership is a "
            "property of the M-device fleet; the paper's fixed "
            "three-worker triple has no notion of join/leave "
            "(use Fleet.from_table2() or topology='star')")
    if churn is not None:
        from repro.core.churn import (DeviceCrash, apply_event,
                                      reference_rows, remap_schedule)

    ops = _loop_ops(topology, model, profile, net, cfg)
    prof = copy.deepcopy(profile)
    # Baseline for the straggler EMA and the simulated "true" speeds.
    # Static fleets: a value-identical copy of ``profile`` (arithmetic
    # unchanged).  Elastic fleets: membership-edited alongside ``prof``
    # so it always describes the *current* fleet at nominal speed.
    base_prof = copy.deepcopy(profile)
    ref = reference_rows(base_prof) if churn is not None else None
    # The solver is a pure function of the profile values, so a caller
    # that already planned this exact (profile, net, B, objective) —
    # Plan.train — can seed the loop and skip the duplicate solve.
    sched = initial_schedule if initial_schedule is not None \
        else ops["solve"](prof).schedule
    params = model.init(jax.random.PRNGKey(cfg.seed))
    wall = 0.0
    start = 0
    resumed_from = None
    churn_log: List[Dict[str, Any]] = []

    manager = CheckpointManager(cfg.ckpt_dir, cfg.keep) \
        if cfg.ckpt_dir and cfg.ckpt_every else None
    if manager is not None:
        is_star = topology == "star"
        is_tree = topology == "tree"

        def _like(ckpt_step, extra):
            if extra.get("seed") != cfg.seed:
                raise ValueError(
                    f"checkpoint seed {extra.get('seed')} does not "
                    f"match cfg.seed {cfg.seed}: refusing to resume a "
                    "different run")
            names = extra["worker_names"] if is_star else None
            rows = len(names) if is_star \
                else np.asarray(profile.L_f).shape[0]
            cols = np.asarray(profile.L_f).shape[1]

            def grid():
                return {k: np.zeros((rows, cols))
                        for k in ("L_f", "L_b", "L_u")}

            like = {"params": model.init(jax.random.PRNGKey(cfg.seed)),
                    "prof": grid()}
            if is_star:
                like["base"] = grid()
                like["ref"] = {k: np.zeros(cols)
                               for k in ("L_f", "L_b", "L_u")}
            return like

        ckpt_step, tree, extra = manager.restore_latest_with(_like)
        if ckpt_step is not None:
            start = resumed_from = ckpt_step
            params = tree["params"]
            wall = float(extra["wall"])
            sched = _sched_from_json(extra["sched"])
            # Star membership may have churned, so names come from the
            # checkpoint; tree/triple fleets have fixed membership and
            # rebuild from the caller's template.
            names = tuple(extra["worker_names"]) if is_star else \
                (profile.worker_names if is_tree else None)
            prof = _profile_from_arrays(profile, names, tree["prof"])
            if is_star:
                from repro.core.cost_model import StarNetwork
                base_prof = _profile_from_arrays(profile, names,
                                                 tree["base"])
                net = StarNetwork(
                    bw_de=np.asarray(extra["bw_de"], dtype=np.float64),
                    bw_ec=float(extra["bw_ec"]))
                ref = (np.asarray(tree["ref"]["L_f"]),
                       np.asarray(tree["ref"]["L_b"]),
                       np.asarray(tree["ref"]["L_u"]))
            ops = _loop_ops(topology, model, prof, net, cfg)

    history = []
    losses = []
    for step in range(start, cfg.total_steps):
        prev_sched = sched
        events = churn.events_at(step) if churn is not None else ()
        if events:
            # A crash kills the in-flight attempt: survivors discover it
            # at the barrier after ~one fill of the pre-crash schedule
            # at baseline speeds, then re-run the step on the new fleet.
            lost = ops["fill"](base_prof, sched) \
                if any(isinstance(e, DeviceCrash) for e in events) \
                else 0.0
            wall += lost
            for ev in events:
                prof, base_prof, net, _ = apply_event(prof, base_prof,
                                                      net, ref, ev)
            # ops closures capture (membership, net) — rebuild on churn
            ops = _loop_ops(topology, model, prof, net, cfg)
            warm = remap_schedule(sched, prof)
            t0 = time.perf_counter()
            res = ops["solve"](prof, warm)
            resolve_s = time.perf_counter() - t0
            sched = res.schedule
            churn_log.append({
                "step": step,
                "events": [f"{type(e).__name__}:{e.name}"
                           for e in events],
                "m": len(ops["names"]) - 2,
                "warm": warm is not None, "lost_s": lost,
                "resolve_s": resolve_s, "n_pruned": res.n_pruned,
                "n_candidates": res.n_candidates})
        slow = worker_slowdown(step) if worker_slowdown else {}
        if worker_slowdown is not None and step > 0 and \
                step % cfg.resched_every == 0:
            _ema_profile_update(prof, base_prof, slow, ops["names"],
                                cfg.ema)
            sched = ops["solve"](prof, sched).schedule
        # timing from the cost model under the *actual* current speeds
        true_prof = copy.deepcopy(base_prof)
        widx = ops["widx"]
        for w, factor in (slow or {}).items():
            if w not in widx:   # straggler report for a departed device
                continue
            i = widx[w]
            true_prof.L_f[i] *= factor
            true_prof.L_b[i] *= factor
            true_prof.L_u[i] *= factor
        if hasattr(true_prof, "_prefix"):   # deepcopy carries the cache
            del true_prof._prefix
        if cfg.pipeline_depth > 1 and step % cfg.pipeline_depth != 0 \
                and sched == prev_sched:
            wall += ops["period"](true_prof, sched)
        else:   # window head or pipe broken by a re-schedule: pay fill
            wall += ops["fill"](true_prof, sched)
        b = data.batch(step)
        # Cached compiled step: static (m_s, m_l, lr), donated params — a
        # reschedule that keeps the cuts reuses the same executable.
        params, loss = ops["step"](
            params, jax.numpy.asarray(b["x"]),
            jax.numpy.asarray(b["labels"]), sched)
        losses.append(float(loss))
        if log and (step + 1) % 10 == 0:
            log(f"{ops['tag']} step {step+1}: loss={losses[-1]:.4f} "
                f"sched=({sched.describe()}) wall={wall:.2f}s")
        history.append({"step": step + 1, "loss": losses[-1],
                        "wall": wall, **ops["hist"](sched),
                        "sched": sched})
        if manager is not None and (step + 1) % cfg.ckpt_every == 0:
            tree = {"params": params, "prof": _prof_arrays(prof)}
            extra = {"step": step + 1, "wall": wall, "seed": cfg.seed,
                     "topology": topology,
                     "sched": _sched_to_json(sched)}
            if topology == "star":
                rows = ref if ref is not None else (
                    np.asarray(base_prof.L_f[0]),
                    np.asarray(base_prof.L_b[0]),
                    np.asarray(base_prof.L_u[0]))
                tree["base"] = _prof_arrays(base_prof)
                tree["ref"] = {"L_f": np.asarray(rows[0]),
                               "L_b": np.asarray(rows[1]),
                               "L_u": np.asarray(rows[2])}
                extra["worker_names"] = list(prof.worker_names)
                extra["bw_de"] = [float(x)
                                  for x in np.asarray(net.bw_de)]
                extra["bw_ec"] = float(net.bw_ec)
            manager.save(step + 1, tree, extra=extra)
        if cfg.fail_at is not None and step + 1 == cfg.fail_at:
            raise InjectedFailure(
                f"injected failure after step {step+1}")
    return {"params": params, "history": history, "wall": wall,
            "final_schedule": sched, "resumed_from": resumed_from,
            "churn_log": churn_log}


def run_hier_loop(cfg: HierLoopConfig, model, profile, net, data,
                  worker_slowdown: Optional[Callable[[int], Dict[str, float]]]
                  = None, log: Optional[Callable[[str], None]] = None
                  ) -> Dict[str, Any]:
    """Deprecated shim over the facade: ``repro.api.plan(model,
    Fleet.from_profile(profile, net), B).train(data, ...)``.  Results —
    trained params, history, wall clock — are bit-identical to the
    historical three-worker loop."""
    from repro.core._deprecation import warn_deprecated
    warn_deprecated(
        "repro.train.loop.run_hier_loop()",
        "repro.api.plan(model, Fleet.from_profile(profile, net), "
        "B).train(data, steps=...)")
    from repro import api
    p = api.plan(model, api.Fleet.from_profile(profile, net), cfg.batch,
                 objective=cfg.objective,
                 pipeline_depth=cfg.pipeline_depth)
    return p.train(data, steps=cfg.total_steps, lr=cfg.lr,
                   resched_every=cfg.resched_every, ema=cfg.ema,
                   seed=cfg.seed, worker_slowdown=worker_slowdown, log=log)


def run_multi_hier_loop(cfg: HierLoopConfig, model, profile, net, data,
                        worker_slowdown: Optional[
                            Callable[[int], Dict[str, float]]] = None,
                        log: Optional[Callable[[str], None]] = None
                        ) -> Dict[str, Any]:
    """Deprecated shim over the facade (M-device variant): see
    :func:`run_hier_loop`."""
    from repro.core._deprecation import warn_deprecated
    warn_deprecated(
        "repro.train.loop.run_multi_hier_loop()",
        "repro.api.plan(model, Fleet.from_profile(profile, net), "
        "B).train(data, steps=...)")
    from repro import api
    p = api.plan(model, api.Fleet.from_profile(profile, net), cfg.batch,
                 objective=cfg.objective,
                 pipeline_depth=cfg.pipeline_depth)
    return p.train(data, steps=cfg.total_steps, lr=cfg.lr,
                   resched_every=cfg.resched_every, ema=cfg.ema,
                   seed=cfg.seed, worker_slowdown=worker_slowdown, log=log)
