"""Facade equivalence suite (DESIGN.md §9): the ``Fleet``/``Plan`` front
door vs the legacy forked surfaces.

Four claim families:

* **Old vs new, bit-identical** — every deprecated entry point
  (``solve``/``solve_multi``, ``t_total*``, ``simulate_iteration*``,
  ``run_*_hier_loop``) returns exactly what the facade returns, and the
  facade returns exactly what the retained topology-native oracles
  return — schedules, costs, periods, DES traces and *trained params* —
  at M = 1 and M >= 2, across the Table II profiles and one LM family.
* **Cross-topology M=1** — a star-native plan at M = 1 is bit-identical
  to the triple-native plan for the latency objective (the deep DESIGN.md
  §6 invariant, now asserted *through the facade*).
* **Deprecation contract** — each shim emits one DeprecationWarning
  naming the exact ``repro.api`` replacement; the facade itself emits
  none (the ``pytest.ini`` filter turns in-repo uses into errors).
* **Surface** — ``repro`` / ``repro.core`` export exactly
  ``Fleet``/``Plan``/``plan``/``as_layerstack``; ``Plan.explain()`` is
  snapshot-stable; the ``python -m repro.api --explain`` CLI runs.
"""
import warnings

import numpy as np
import pytest

import repro
import repro.core
from repro.api import Fleet, Plan, plan
from repro.core import cost_model, pipeline, scheduler, simulator
from repro.core.cost_model import (HierProfile, MultiProfile, MultiSchedule,
                                   Network, Schedule, StarNetwork, WIDX)
from repro.core.fleet import (FLEET_SLOWDOWNS, FLEET_UPLINK_MBPS,
                              LM_FLEET_SLOWDOWNS, LM_FLEET_UPLINK_MBPS)

MBPS = 1e6 / 8.0

TABLE2_LAYERS = {"lenet5": 5, "alexnet": 8, "vgg16": 16}


def synthetic_profile(n: int) -> HierProfile:
    rng = np.random.default_rng(0)
    speed = np.array([[1.0], [0.12], [0.01]])
    base = rng.uniform(5e-3, 5e-2, (1, n))
    return HierProfile(
        layer_names=tuple(f"l{i}" for i in range(n)),
        L_f=base * speed, L_b=2 * base * speed, L_u=0.5 * base * speed,
        MP=rng.uniform(1e5, 5e7, n), MO=rng.uniform(1e4, 2e6, n),
        sample_bytes=3073.0)


def triple_fleet(n: int, ec_mbps: float = 3.0) -> Fleet:
    return Fleet.from_profile(
        synthetic_profile(n), Network(bw_de=5.0 * MBPS,
                                      bw_ec=ec_mbps * MBPS))


def star_fleet_m1(n: int, ec_mbps: float = 3.0) -> Fleet:
    return Fleet.from_profile(
        MultiProfile.from_hier(synthetic_profile(n), (1.0,)),
        StarNetwork.from_network(Network(bw_de=5.0 * MBPS,
                                         bw_ec=ec_mbps * MBPS), 1))


def star_fleet(n: int, scales, seed: int = 0) -> Fleet:
    rng = np.random.default_rng(seed)
    m = len(scales)
    return Fleet.from_profile(
        MultiProfile.from_hier(synthetic_profile(n), scales),
        StarNetwork(bw_de=rng.uniform(2.0, 5.0, m) * MBPS,
                    bw_ec=3.0 * MBPS))


def _tiny_mlp():
    from repro.models.cnn import DenseSpec, LayeredModel
    specs = tuple(DenseSpec(f"fc{i}", 16) for i in range(4)) + \
        (DenseSpec("out", 5, relu=False),)
    return LayeredModel("tiny_mlp", specs, (8,), 5)


# ---------------------------------------------------------------------------
# Schedules and costs: facade == topology-native oracles, both topologies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,n", sorted(TABLE2_LAYERS.items()))
@pytest.mark.parametrize("backend", ["batched", "reference"])
def test_plan_bit_identical_to_oracles_table2(name, n, backend):
    """plan() on a triple fleet IS the 3-worker engine, and on a star
    fleet at M=1 it is bit-identical to it — through the facade."""
    prof = synthetic_profile(n)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    oracle = scheduler._solve_3w(prof, net, 64, backend=backend)
    p3 = plan(None, triple_fleet(n), 64, backend=backend)
    assert isinstance(p3.schedule, Schedule)
    assert p3.schedule == oracle.schedule
    assert p3.t_total == oracle.t_total
    assert p3.result.n_candidates == oracle.n_candidates
    assert p3.result.n_pruned == oracle.n_pruned
    ps = plan(None, star_fleet_m1(n), 64, backend=backend)
    assert isinstance(ps.schedule, MultiSchedule)
    assert ps.schedule.to_schedule() == oracle.schedule
    assert ps.t_total == oracle.t_total
    assert ps.result.n_candidates == oracle.n_candidates
    if backend == "batched":   # the scalar 3-worker oracle never prunes
        assert ps.result.n_pruned == oracle.n_pruned
    # the unified view and the describe strings collapse too
    assert p3.multi_schedule == ps.schedule
    assert p3.schedule.describe() == ps.schedule.describe()


def test_solve_shims_bit_identical_and_warn():
    prof = synthetic_profile(6)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    p = plan(None, triple_fleet(6), 48)
    with pytest.warns(DeprecationWarning, match=r"repro\.api\.plan"):
        old = scheduler.solve(prof, net, 48)
    assert isinstance(old.schedule, Schedule)
    assert old.schedule == p.schedule and old.t_total == p.t_total
    mprof = MultiProfile.from_hier(prof, (1.0, 1.7))
    mnet = StarNetwork(bw_de=np.array([4.0, 3.0]) * MBPS, bw_ec=3.0 * MBPS)
    pm = plan(None, Fleet.from_profile(mprof, mnet), 48)
    with pytest.warns(DeprecationWarning, match=r"repro\.api\.plan"):
        old_m = scheduler.solve_multi(mprof, mnet, 48)
    assert old_m.schedule == pm.schedule
    assert old_m.t_total == pm.t_total
    assert old_m.n_lp_refine == pm.result.n_lp_refine


def test_solve_shim_exotic_args_keep_working():
    """origin/workers corners the facade does not model fall back to the
    retained 3-worker engine (bit-identical to the pre-facade code)."""
    prof = synthetic_profile(4)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    with pytest.warns(DeprecationWarning):
        r = scheduler.solve(prof, net, 16, origin="edge")
    assert r.schedule == scheduler._solve_3w(prof, net, 16,
                                             origin="edge").schedule
    with pytest.raises(ValueError):
        with pytest.warns(DeprecationWarning):
            scheduler.solve(prof, net, 8, backend="cplex")


def test_t_total_shims_collapse_onto_multi_bitwise():
    """The deprecated 3-worker cost entry points now evaluate the star
    model — bit-identical to the retained 3-worker oracle on every
    mapping/cut (the §6 invariant exercised through the shims)."""
    import itertools
    prof = synthetic_profile(5)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    rng = np.random.default_rng(3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for wo, ws, wl in itertools.permutations(
                ("device", "edge", "cloud"), 3):
            ms, ml = sorted(rng.integers(0, 6, 2))
            b = rng.multinomial(32, [1 / 3] * 3)
            bo, bs, bl = (int(v) for v in b)
            if ms == 0:
                bo, bs = bo + bs, 0
            if ml == 0:
                bo, bl = bo + bl, 0
            sched = Schedule(wo, ws, wl, int(ms), int(ml), bo, bs, bl)
            ref = cost_model._t_total(prof, net, sched)
            got = cost_model.t_total(prof, net, sched)
            assert got.total == ref.total
            assert got.t_f1 == ref.t_f1 and got.t_update == ref.t_update
            tb = cost_model.t_total_batch(
                prof, net, np.array([WIDX[wo]]), np.array([WIDX[ws]]),
                np.array([WIDX[wl]]), np.array([int(ms)]),
                np.array([int(ml)]), np.array([[bo, bs, bl]]))
            assert tb[0] == ref.total
        # degenerate all-on-one schedules fall back to the 3-worker body
        degen = Schedule("edge", "edge", "edge", 0, 0, 16, 0, 0)
        assert cost_model.t_total(prof, net, degen).total == \
            cost_model._t_total(prof, net, degen).total
        # t_total_multi shim == retained engine
        mprof = MultiProfile.from_hier(prof, (1.0, 1.5))
        mnet = StarNetwork(bw_de=np.array([4.0, 3.0]) * MBPS,
                           bw_ec=2.0 * MBPS)
        msched = MultiSchedule("edge", "cloud",
                               mprof.device_names, (1, 2), 3, 10, (8, 6), 8)
        assert cost_model.t_total_multi(mprof, mnet, msched).total == \
            cost_model._t_total_multi(mprof, mnet, msched).total


# ---------------------------------------------------------------------------
# Simulated traces and periods
# ---------------------------------------------------------------------------

def test_simulate_matches_native_des_and_shims():
    p3 = plan(None, triple_fleet(5), 64)
    want3 = simulator._simulate_iteration(p3.profile, p3.network,
                                          p3.schedule)
    assert p3.simulate() == want3
    assert p3.simulate(K=4) == simulator.simulate_pipeline(
        p3.profile, p3.network, p3.schedule, 4)
    with pytest.warns(DeprecationWarning, match=r"Plan\.simulate|simulate"):
        assert simulator.simulate_iteration(
            p3.profile, p3.network, p3.schedule) == want3

    pm = plan(None, star_fleet(5, (1.0, 1.6)), 48)
    want_m = simulator._simulate_iteration_multi(pm.profile, pm.network,
                                                 pm.schedule)
    assert pm.simulate() == want_m
    assert pm.simulate(K=3) == simulator.simulate_pipeline(
        pm.profile, pm.network, pm.schedule, 3)
    with pytest.warns(DeprecationWarning):
        assert simulator.simulate_iteration_multi(
            pm.profile, pm.network, pm.schedule) == want_m


def test_t_period_and_pipeline_time_native():
    p3 = plan(None, triple_fleet(5), 64, pipeline_depth=8)
    assert p3.t_period == pipeline.t_period(p3.profile, p3.network,
                                            p3.schedule)
    assert p3.pipeline_time() == pipeline.t_pipeline(
        p3.profile, p3.network, p3.schedule, 8)
    pm = plan(None, star_fleet(5, (1.0, 1.3)), 32)
    assert pm.t_period == pipeline.t_period_multi(pm.profile, pm.network,
                                                  pm.schedule)


def test_throughput_objective_through_facade():
    thr = plan(None, triple_fleet(6), 48, objective="throughput")
    want = scheduler._solve_3w(synthetic_profile(6),
                               Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS),
                               48, objective="throughput")
    assert thr.schedule == want.schedule
    assert thr.t_period == want.t_period
    lat = plan(None, triple_fleet(6), 48)
    assert thr.t_period <= lat.t_period


# ---------------------------------------------------------------------------
# Execution: step_fn and train — trained params bit-identical
# ---------------------------------------------------------------------------

def _img_data(model, B):
    from repro.data.pipeline import SyntheticImages
    return SyntheticImages(model.input_shape, model.num_classes, B, seed=0)


def _cnn_fleet(model, m=1, topology="auto"):
    from repro.core.profiler import analytic_profile, multi_analytic_profile
    if topology == "triple":
        return Fleet.from_profile(analytic_profile(model),
                                  Network(bw_de=4.0 * MBPS,
                                          bw_ec=2.0 * MBPS))
    prof = multi_analytic_profile(
        model, device_slowdowns=tuple(1.0 + 0.2 * i for i in range(m)))
    net = StarNetwork(bw_de=np.full(m, 4.0) * MBPS, bw_ec=2.0 * MBPS)
    return Fleet.from_profile(prof, net)


def test_step_fn_bit_identical_to_legacy_jitted_step():
    import jax
    import jax.numpy as jnp
    from repro.core.hybrid_step import jitted_hybrid_step, split_batch
    model = _tiny_mlp()
    p = plan(model, _cnn_fleet(model, topology="triple"), 16)
    sched = MultiSchedule.from_schedule(p.schedule)
    data = _img_data(model, 16)
    b = data.batch(0)
    x, y = jnp.asarray(b["x"]), jnp.asarray(b["labels"])
    params = model.init(jax.random.PRNGKey(0))
    copy = lambda t: jax.tree.map(jnp.array, t)  # donated args need copies
    legacy = jitted_hybrid_step(model, sched.m_s, sched.m_l, 0.05)
    new_p, new_l = p.step_fn(lr=0.05)(copy(params), x, y)
    old_p, old_l = legacy(copy(params), split_batch(x, y, sched))
    assert float(new_l) == float(old_l)
    for a, b2 in zip(jax.tree.leaves(new_p), jax.tree.leaves(old_p)):
        assert (np.asarray(a) == np.asarray(b2)).all()


def test_step_fn_of_triple_star_and_tree_fleets_is_one_program(
        monkeypatch):
    """Fleets of one profile whose schedules are equal run one compiled
    step: the triple, the M=1 star and the E=1 tree share a single
    ``_JIT_CACHE`` entry (the step has no topology fork)."""
    import dataclasses

    from repro.core import hybrid_step as hs
    from repro.core.layerstack import as_layerstack
    monkeypatch.setattr(hs, "_JIT_CACHE", hs._JitStepCache())
    stack = as_layerstack(_tiny_mlp())
    sched = Schedule("cloud", "device", "edge", 2, 3, 6, 5, 5)
    fns = []
    for topology in ("triple", "star", "tree"):
        p = plan(stack, Fleet.from_table2(topology=topology), 16)
        assert p.fleet.topology == topology
        s = sched if topology == "triple" else \
            MultiSchedule.from_schedule(sched)
        p = dataclasses.replace(
            p, result=dataclasses.replace(p.result, schedule=s))
        p.step_fn(lr=0.05)
        fns.append(hs.jitted_hybrid_step(stack, (2,), 3, 0.05))
    assert len(hs._JIT_CACHE) == 1
    assert fns[0] is fns[1] is fns[2]


def test_trained_params_bit_identical_triple_vs_star_m1():
    """Plan.train at M=1 is bit-identical across topology engines —
    schedules, wall clock, losses AND trained parameters — including
    through a straggle-and-heal window that exercises the online
    re-scheduler."""
    import jax
    model = _tiny_mlp()

    def slowdown(step):
        return {"edge": 20.0} if 3 <= step < 6 else {}

    outs = []
    for topology in ("triple", "star"):
        out = plan(model, _cnn_fleet(model, topology=topology), 24).train(
            _img_data(model, 24), steps=8, lr=0.05, resched_every=3,
            worker_slowdown=slowdown)
        outs.append(out)
    a, b = outs
    assert a["wall"] == b["wall"]
    assert [h["loss"] for h in a["history"]] == \
        [h["loss"] for h in b["history"]]
    assert b["final_schedule"].to_schedule() == a["final_schedule"]
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        assert (np.asarray(x) == np.asarray(y)).all()


def test_run_hier_loop_shims_route_through_facade():
    import jax
    model = _tiny_mlp()
    fleet = _cnn_fleet(model, topology="triple")
    want = plan(model, fleet, 16).train(_img_data(model, 16), steps=4,
                                        lr=0.05)
    from repro.train.loop import HierLoopConfig, run_hier_loop
    cfg = HierLoopConfig(total_steps=4, batch=16, lr=0.05)
    with pytest.warns(DeprecationWarning, match=r"\.train\(data"):
        old = run_hier_loop(cfg, model, fleet.profile_for(model),
                            fleet.network(), _img_data(model, 16))
    assert old["wall"] == want["wall"]
    assert [h["loss"] for h in old["history"]] == \
        [h["loss"] for h in want["history"]]
    assert isinstance(old["history"][0]["m_s"], int)  # triple history shape
    for x, y in zip(jax.tree.leaves(old["params"]),
                    jax.tree.leaves(want["params"])):
        assert (np.asarray(x) == np.asarray(y)).all()

    fleet2 = _cnn_fleet(model, m=2, topology="star")
    want2 = plan(model, fleet2, 18).train(_img_data(model, 18), steps=3,
                                          lr=0.05)
    from repro.train.loop import run_multi_hier_loop
    cfg2 = HierLoopConfig(total_steps=3, batch=18, lr=0.05)
    with pytest.warns(DeprecationWarning):
        old2 = run_multi_hier_loop(cfg2, model, fleet2.profile_for(model),
                                   fleet2.network(), _img_data(model, 18))
    assert [h["loss"] for h in old2["history"]] == \
        [h["loss"] for h in want2["history"]]
    assert old2["final_schedule"] == want2["final_schedule"]


# ---------------------------------------------------------------------------
# LM family through the facade
# ---------------------------------------------------------------------------

def test_lm_family_plans_and_steps_through_facade():
    import jax
    from repro.models.lm.layerstack import lm_layerstack
    from repro.models.lm.model import LMConfig
    cfg = LMConfig("api-test", "dense", n_layers=2, d_model=32, n_heads=2,
                   n_kv_heads=1, d_ff=64, vocab=64)
    stack = lm_layerstack(cfg, seq_len=16)
    fleet = Fleet.lm_default(m=2)
    p = plan(stack, fleet, 8)
    with pytest.warns(DeprecationWarning):
        old = scheduler.solve_multi(p.profile, p.network, 8)
    assert old.schedule == p.schedule and old.t_total == p.t_total
    # the plan executes: one exact hybrid-SGD step on the LM stack
    params = p.init_params(jax.random.PRNGKey(0))
    x, y = stack.dummy_batch(jax.random.PRNGKey(1), 8)
    params, loss = p.step_fn(lr=0.01)(params, x, y)
    assert np.isfinite(float(loss))
    assert p.simulate() > 0


# ---------------------------------------------------------------------------
# Constructors: from_table2 / lm_default match the shared hardware tables
# ---------------------------------------------------------------------------

def test_from_table2_matches_direct_construction():
    from repro.core.profiler import PAPER_TESTBED, analytic_profile
    from repro.models.cnn import lenet5
    model = lenet5()
    fleet = Fleet.from_table2(model="lenet5", m=3, edge_cloud_mbps=3.0,
                              topology="star")
    prof = fleet.profile_for(model)
    want = MultiProfile.from_hier(analytic_profile(model, PAPER_TESTBED),
                                  FLEET_SLOWDOWNS[:3])
    assert (prof.L_f == want.L_f).all() and (prof.L_u == want.L_u).all()
    assert prof.worker_names == want.worker_names
    net = fleet.network()
    assert (net.bw_de == np.array(FLEET_UPLINK_MBPS[:3]) * MBPS).all()
    # M=1 auto-resolves to the paper's exact triple
    f1 = Fleet.from_table2(model="lenet5")
    assert f1.topology == "triple"
    assert isinstance(f1.network(), Network)
    assert f1.network().bw_de == 5.0 * MBPS


def test_lm_default_matches_shared_tables():
    fleet = Fleet.lm_default(m=2)
    assert fleet.topology == "star"
    assert fleet.device_slowdowns == LM_FLEET_SLOWDOWNS[:2]
    net = fleet.network()
    assert (net.bw_de == np.array(LM_FLEET_UPLINK_MBPS[:2]) * MBPS).all()
    assert fleet.sample_bytes == 2e6


def test_benchmark_fleet_helpers_unchanged():
    """benchmarks.common now delegates to Fleet — same arrays as ever."""
    from benchmarks.common import fleet_profile, star_network
    from repro.core.profiler import PAPER_TESTBED, analytic_profile
    from repro.models.cnn import lenet5
    prof = fleet_profile("lenet5", 2)
    want = MultiProfile.from_hier(analytic_profile(lenet5(),
                                                   PAPER_TESTBED),
                                  FLEET_SLOWDOWNS[:2])
    assert (prof.L_f == want.L_f).all()
    net = star_network(2, 3.0)
    assert (net.bw_de == np.array(FLEET_UPLINK_MBPS[:2]) * MBPS).all()
    assert net.bw_ec == 3.0 * MBPS


# ---------------------------------------------------------------------------
# Public surface, warnings hygiene, explain snapshot, CLI
# ---------------------------------------------------------------------------

def test_public_surface_exports():
    assert repro.__all__ == ["Fleet", "Plan", "plan", "plan_many",
                             "as_layerstack"]
    assert repro.core.__all__ == ["Fleet", "Plan", "plan", "plan_many",
                                  "as_layerstack"]
    assert repro.Fleet is Fleet and repro.core.Fleet is Fleet
    assert repro.plan is plan and repro.core.plan is plan
    from repro.api import plan_many
    assert repro.plan_many is plan_many
    assert repro.core.plan_many is plan_many
    assert repro.Plan is Plan
    from repro.core.layerstack import as_layerstack
    assert repro.as_layerstack is as_layerstack
    assert repro.core.as_layerstack is as_layerstack
    with pytest.raises(AttributeError):
        repro.nonexistent_name


def test_facade_emits_no_deprecation_warnings():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        p = plan(None, triple_fleet(5), 32)
        p.simulate()
        p.simulate(K=2)
        p.baseline("edge")
        p.explain()
        pm = plan(None, star_fleet(5, (1.0, 1.4)), 32)
        pm.simulate()
        pm.baseline("cloud")
        pm.explain()
    ours = [x for x in w if issubclass(x.category, DeprecationWarning)
            and str(x.message).startswith("repro.")]
    assert ours == []


def test_every_shim_warns_with_exact_replacement():
    prof = synthetic_profile(4)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    mprof = MultiProfile.from_hier(prof, (1.0,))
    mnet = StarNetwork.from_network(net, 1)
    sched = scheduler._solve_3w(prof, net, 16).schedule
    msched = MultiSchedule.from_schedule(sched)
    calls = [
        lambda: scheduler.solve(prof, net, 16),
        lambda: scheduler.solve_multi(mprof, mnet, 16),
        lambda: cost_model.t_total(prof, net, sched),
        lambda: cost_model.t_total_multi(mprof, mnet, msched),
        lambda: cost_model.t_total_batch(
            prof, net, np.array([0]), np.array([1]), np.array([2]),
            np.array([0]), np.array([0]), np.array([[16, 0, 0]])),
        lambda: cost_model.t_total_multi_batch(
            mprof, mnet, np.array([0]), np.array([[1]]), np.array([2]),
            np.array([[0]]), np.array([0]), np.array([[16, 0, 0]])),
        lambda: simulator.simulate_iteration(prof, net, sched),
        lambda: simulator.simulate_iteration_multi(mprof, mnet, msched),
    ]
    for call in calls:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            call()
        ours = [x for x in w if str(x.message).startswith("repro.")]
        assert len(ours) == 1, [str(x.message) for x in w]
        assert "repro.api" in str(ours[0].message)
        assert issubclass(ours[0].category, DeprecationWarning)


def test_plan_argument_errors():
    with pytest.raises(ValueError, match="pipeline_depth"):
        plan(None, triple_fleet(4), 8, pipeline_depth=0)
    with pytest.raises(ValueError, match="unknown scheduler objective"):
        plan(None, triple_fleet(4), 8, objective="goodput")
    p = plan(None, triple_fleet(4), 8)
    with pytest.raises(ValueError, match="without a model"):
        p.step_fn()
    with pytest.raises(ValueError, match="pass a model"):
        Fleet.from_table2().profile_for(None)
    with pytest.raises(ValueError, match="topology"):
        Fleet(topology="ring")
    with pytest.raises(ValueError, match="exactly one device"):
        Fleet.from_table2(m=2, topology="triple")
    prof = synthetic_profile(3)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    with pytest.raises(ValueError, match="triple-native"):
        Fleet.from_profile(prof, net, topology="star")
    with pytest.raises(ValueError, match="star-native"):
        Fleet.from_profile(MultiProfile.from_hier(prof, (1.0,)),
                           StarNetwork.from_network(net, 1),
                           topology="triple")


EXPLAIN_SNAPSHOT = """\
HierTrain plan — model=lenet5  fleet[M=1 (triple; uplinks 5 Mbps, \
backhaul 3 Mbps)]
  batch B=32  objective=latency  backend=batched  wire=none
  schedule: o=device(b=32) s=edge(m=0,b=0) l=cloud(m=0,b=0)
  cuts: m_s=0  m_l=0  of N=5 layers
  predicted: T_total=0.0951891s  T_period=0.0951891s
  phases (s): f1=0 b1=0 f2=0 b2=0 f3=0.03686 b3=0.05771 update=0.000624
  comm (s): input=0 activation=0 weight-sync=0
  baselines: all-edge=0.16701s (1.75x)  all-cloud=0.422228s (4.44x)
  search: 126 candidates, 0 pruned, 126 LPs"""


def test_explain_snapshot():
    from repro.models.cnn import lenet5
    p = plan(lenet5(), Fleet.from_table2(model="lenet5", m=1,
                                         edge_cloud_mbps=3.0), 32)
    assert p.explain() == EXPLAIN_SNAPSHOT


def test_cli_explain_smoke(capsys):
    from repro import api
    assert api.main(["--explain", "lenet5", "--batch", "16"]) == 0
    out = capsys.readouterr().out
    assert "HierTrain plan" in out and "simulated (DES)" in out
    with pytest.raises(SystemExit):
        api.main(["--explain", "resnet"])
