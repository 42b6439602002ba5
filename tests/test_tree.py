"""Multi-edge tree generalization (DESIGN.md §12): equivalence and
validity suite.

Mirrors the star suite's invariant families one level up:

* **E=1 exactness** — the tree cost model, scheduler, DES and hybrid
  step must reproduce the star path *bit-for-bit* (same schedules, same
  ``T_total``/``T_period``, identical DES makespans and parameter
  updates), the same way the star at M=1 reproduces the triple.
* **Model validity at E > 1** — the DES makespan matches the tree
  Eq.-12 generalization within the Fig.-6 tolerance on genuinely-tree
  schedules (per-edge backhaul pipes, foreign-edge relays).
* **Exact SGD at E > 1** — the tree hybrid step with per-edge
  activation merges is batch-B SGD to float32 tolerance against the
  single-machine reference.
* **Facade** — ``topology="tree"`` fleet validation (``edge_of``
  contiguity, duplicate worker names), churn rejection naming the
  topology, and the E=1 tree train loop matching the star loop.
"""
import jax
import numpy as np
import pytest
from tests._compat import given, settings, st

from repro.core.cost_model import (MultiProfile, MultiSchedule, StarNetwork,
                                   TreeNetwork, TreeProfile, t_total_multi,
                                   t_total_tree)
from repro.core.pipeline import t_period_multi, t_period_tree
from repro.core.scheduler import solve_multi
from repro.core.simulator import _simulate_iteration_multi

MBPS = 1e6 / 8.0


def _tiny_mlp():
    from repro.models.cnn import DenseSpec, LayeredModel
    specs = tuple(DenseSpec(f"fc{i}", 16) for i in range(4)) + \
        (DenseSpec("out", 5, relu=False),)
    return LayeredModel("tiny_mlp", specs, (8,), 5)


def _star(m=4, seed=0):
    from repro.core.profiler import multi_analytic_profile
    model = _tiny_mlp()
    slowdowns = tuple(1.0 + 0.3 * i for i in range(m))
    prof = multi_analytic_profile(model, device_slowdowns=slowdowns)
    rng = np.random.default_rng(seed)
    net = StarNetwork(bw_de=rng.uniform(2.0, 5.0, m) * MBPS,
                      bw_ec=2.0 * MBPS)
    return model, prof, net


def _tree(m=4, e=2, seed=0, edge_scales=None, backhauls=None):
    model, prof, net = _star(m, seed)
    edge_of = tuple(i * e // m for i in range(m))
    tprof = TreeProfile.from_multi(prof, n_edges=e,
                                   edge_scales=edge_scales)
    bh = np.asarray(backhauls, np.float64) * MBPS if backhauls is not None \
        else np.full(e, 2.0) * MBPS
    tnet = TreeNetwork(bw_de=net.bw_de, bw_ec=bh, edge_of=edge_of)
    return model, tprof, tnet


# ---------------------------------------------------------------------------
# E=1 exactness: scheduler, cost model, period, DES
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 3])
@pytest.mark.parametrize("objective", ["latency", "throughput"])
def test_e1_scheduler_bit_identical_to_star(m, objective):
    _, prof, net = _star(m)
    tprof = TreeProfile.from_multi(prof, n_edges=1)
    tnet = TreeNetwork.from_star(net)
    rs = solve_multi(prof, net, B=24, objective=objective)
    rt = solve_multi(tprof, tnet, B=24, objective=objective)
    assert rt.schedule == rs.schedule
    assert rt.t_total == rs.t_total          # bit-for-bit, not approx
    assert rt.n_candidates == rs.n_candidates
    assert rt.n_pruned == rs.n_pruned
    sched = rs.schedule
    assert t_total_tree(tprof, tnet, sched).total == \
        t_total_multi(prof, net, sched).total
    assert t_period_tree(tprof, tnet, sched) == \
        t_period_multi(prof, net, sched)


def test_e1_des_trace_bit_identical_to_star():
    """The tree DES at E=1 builds the same pipes with the same durations
    as the star DES — makespans match bitwise on both objectives and on
    a hand-built upload-heavy schedule."""
    _, prof, net = _star(3)
    tprof = TreeProfile.from_multi(prof, n_edges=1)
    tnet = TreeNetwork.from_star(net)
    scheds = [solve_multi(prof, net, B=24).schedule,
              MultiSchedule(worker_o="cloud", worker_l="edge",
                            s_workers=("device_0", "device_1", "device_2"),
                            m_s=(2, 1, 0), m_l=4, b_o=10, b_s=(8, 6, 0),
                            b_l=0)]
    for sched in scheds:
        assert _simulate_iteration_multi(tprof, tnet, sched) == \
            _simulate_iteration_multi(prof, net, sched)


def test_treeprofile_roundtrip_and_names():
    _, prof, _ = _star(2)
    tp = TreeProfile.from_multi(prof, n_edges=1)
    assert tp.worker_names == prof.worker_names      # "edge" at E=1
    back = tp.to_multi()
    np.testing.assert_array_equal(back.L_f, prof.L_f)
    tp2 = TreeProfile.from_multi(prof, n_edges=2)
    assert tp2.edge_names == ("edge_0", "edge_1")
    assert tp2.num_devices == 2 and tp2.num_streams == 3
    with pytest.raises(AssertionError):
        tp2.to_multi()                               # only E=1 reduces


# ---------------------------------------------------------------------------
# E>1 model validity: DES vs the tree Eq. 12
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,backhauls", [(2, (2.0, 1.5)),
                                         (4, (2.0, 1.5, 2.5, 1.0))])
def test_tree_des_matches_cost_model(e, backhauls):
    """On solver-chosen E>1 schedules (per-edge uploads, foreign-edge
    relays) the DES stays within the Fig.-6 validity tolerance of the
    closed form."""
    _, tprof, tnet = _tree(m=4, e=e, backhauls=backhauls)
    res = solve_multi(tprof, tnet, B=24)
    sim = _simulate_iteration_multi(tprof, tnet, res.schedule)
    assert abs(sim - res.t_total) / res.t_total < 0.05


def test_tree_des_matches_cost_model_forced_relays():
    """A hand-built schedule that exercises every tree pipe class:
    cloud uploads, own-edge uploads and foreign-edge relays."""
    _, tprof, tnet = _tree(m=4, e=2, backhauls=(2.0, 1.5))
    sched = MultiSchedule(
        worker_o="cloud", worker_l="device_3",
        s_workers=("device_0", "device_1", "device_2", "edge_0", "edge_1"),
        m_s=(2, 2, 1, 2, 1), m_l=3, b_o=6, b_s=(4, 3, 3, 5, 3), b_l=0)
    t = t_total_tree(tprof, tnet, sched).total
    sim = _simulate_iteration_multi(tprof, tnet, sched)
    assert abs(sim - t) / t < 0.05


# ---------------------------------------------------------------------------
# hybrid step: E=1 bitwise vs star; E>1 exact SGD; per-edge merges
# ---------------------------------------------------------------------------

def _batch(model, B, seed=0):
    import jax.numpy as jnp
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (B,) + model.input_shape, jnp.float32)
    y = jax.random.randint(ky, (B,), 0, model.num_classes)
    return x, y


def test_tree_step_e1_bit_identical_to_star_step():
    """An E=1 tree fleet and its star, pinned to one split schedule
    (two same-cut streams), give bit-identical params and loss through
    ``Plan.step_fn``."""
    import dataclasses

    from repro import api
    model = _tiny_mlp()
    sched = MultiSchedule(worker_o="cloud", worker_l="edge",
                          s_workers=("device_0", "device_1", "device_2"),
                          m_s=(2, 2, 1), m_l=4, b_o=6, b_s=(4, 3, 3),
                          b_l=8)
    x, y = _batch(model, 24, seed=1)
    outs = []
    for fleet in _api_fleets():
        p = api.plan(model, fleet, 24)
        p = dataclasses.replace(
            p, result=dataclasses.replace(p.result, schedule=sched))
        assert p.stream_edges() == (0, 0, 0)
        outs.append(p.step_fn(lr=0.05)(model.init(jax.random.PRNGKey(1)),
                                       x, y))
    (ps, ls), (pt, lt) = outs
    assert float(ls) == float(lt)
    for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(pt)):
        assert (np.asarray(a) == np.asarray(b)).all()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_tree_step_equals_reference_sgd(seed):
    """Random E=2 tree schedules (including same-cut streams split
    across different edges — distinct merge groups) are exact batch-B
    SGD."""
    from repro.core.hybrid_step import (hybrid_step_from_schedule,
                                        reference_sgd_step,
                                        tree_stream_edges)
    rng = np.random.default_rng(seed)
    model = _tiny_mlp()
    N = model.num_layers
    m, e = 4, 2
    _, tprof, tnet = _tree(m=m, e=e, seed=seed % 7)
    B = 16
    S = tprof.num_streams
    names = tprof.worker_names
    m_l = int(rng.integers(0, N + 1))
    m_s = tuple(int(rng.integers(0, m_l + 1)) for _ in range(S))
    splits = rng.multinomial(B, np.ones(S + 2) / (S + 2))
    b_s = [int(v) if m_s[i] > 0 else 0
           for i, v in enumerate(splits[1:1 + S])]
    b_l = int(splits[1 + S]) if m_l > 0 else 0
    b_o = B - sum(b_s) - b_l
    order = rng.permutation(S + 2)
    sched = MultiSchedule(
        worker_o=names[order[0]], worker_l=names[order[1]],
        s_workers=tuple(names[i] for i in order[2:]),
        m_s=m_s, m_l=m_l, b_o=b_o, b_s=tuple(b_s), b_l=b_l)
    x, y = _batch(model, B, seed)
    params = model.init(jax.random.PRNGKey(seed))
    hyb, _ = hybrid_step_from_schedule(
        model, params, x, y, sched, lr=0.05,
        stream_edge=tree_stream_edges(tprof, tnet, sched))
    ref, _ = reference_sgd_step(model, params, x, y, 0.05)
    for pr, ph in zip(ref, hyb):
        np.testing.assert_allclose(pr["w"], ph["w"], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(pr["b"], ph["b"], rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# facade: plan() nativity, fleet validation, churn rejection, training
# ---------------------------------------------------------------------------

def _api_fleets(m=3):
    from repro import api
    _, prof, net = _star(m)
    star = api.Fleet.from_profile(prof, net)
    tree = api.Fleet.from_profile(TreeProfile.from_multi(prof, n_edges=1),
                                  TreeNetwork.from_star(net))
    return star, tree


def test_plan_e1_tree_equals_star_plan():
    from repro import api
    model = _tiny_mlp()
    star, tree = _api_fleets()
    ps = api.plan(model, star, 24)
    pt = api.plan(model, tree, 24)
    assert pt.multi_schedule == ps.multi_schedule
    assert pt.t_total == ps.t_total
    assert pt.t_period == ps.t_period
    assert pt.simulate() == ps.simulate()
    assert pt.simulate(K=4) == ps.simulate(K=4)
    edges = pt.stream_edges()
    assert len(edges) == len(pt.multi_schedule.s_workers)
    assert set(edges) == {0}                     # everything on edge 0


def test_e1_tree_train_loop_bit_identical_to_star():
    from repro import api
    from repro.data.pipeline import SyntheticImages
    model = _tiny_mlp()
    star, tree = _api_fleets()
    data = SyntheticImages(model.input_shape, model.num_classes, 24,
                           seed=0)
    kw = dict(steps=6, seed=3, resched_every=3)
    out_s = api.plan(model, star, 24).train(data, **kw)
    out_t = api.plan(model, tree, 24).train(data, **kw)
    assert out_s["wall"] == out_t["wall"]
    for ha, hb in zip(out_s["history"], out_t["history"]):
        assert ha["loss"] == hb["loss"] and ha["sched"] == hb["sched"]
    for a, b in zip(jax.tree.leaves(out_s["params"]),
                    jax.tree.leaves(out_t["params"])):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_tree_train_loop_e2_runs_and_resumes(tmp_path):
    from repro import api
    from repro.data.pipeline import SyntheticImages
    from repro.train.loop import InjectedFailure
    model, tprof, tnet = _tree(m=4, e=2)
    fleet = api.Fleet.from_profile(tprof, tnet)
    data = SyntheticImages(model.input_shape, model.num_classes, 24,
                           seed=0)
    kw = dict(steps=8, seed=3, resched_every=4)
    ref = api.plan(model, fleet, 24).train(data, **kw)
    assert len(ref["history"]) == 8 and ref["wall"] > 0
    with pytest.raises(InjectedFailure):
        api.plan(model, fleet, 24).train(
            data, ckpt_dir=str(tmp_path), ckpt_every=3, fail_at=7, **kw)
    out = api.plan(model, fleet, 24).train(
        data, ckpt_dir=str(tmp_path), ckpt_every=3, **kw)
    assert out["resumed_from"] == 6
    for a, b in zip(jax.tree.leaves(ref["params"]),
                    jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_rejects_duplicate_worker_names():
    import dataclasses
    from repro import api
    _, prof, net = _star(2)
    # the profile refuses to be built with a duplicate row...
    with pytest.raises(ValueError, match="duplicate worker names"):
        dataclasses.replace(
            prof, worker_names=("device_0", "device_0", "edge", "cloud"))
    # ...and the fleet independently re-checks a pinned profile (belt
    # and braces against a mutated-in-place one)
    prof.worker_names = ("device_0", "device_0", "edge", "cloud")
    with pytest.raises(ValueError, match="duplicate worker names"):
        api.Fleet.from_profile(prof, net)


def test_fleet_tree_spec_validation():
    from repro import api
    with pytest.raises(ValueError, match="edge_of"):
        api.Fleet(device_slowdowns=(1.0, 1.2), uplink_mbps=(5.0, 4.0),
                  topology="tree")
    with pytest.raises(ValueError, match="contiguous"):
        api.Fleet(device_slowdowns=(1.0, 1.2), uplink_mbps=(5.0, 4.0),
                  topology="tree", edge_of=(0, 2))
    with pytest.raises(ValueError, match="one entry per device"):
        api.Fleet(device_slowdowns=(1.0, 1.2), uplink_mbps=(5.0, 4.0),
                  topology="tree", edge_of=(0,))


def test_churn_rejected_on_tree_names_topology():
    from repro import api
    from repro.core.churn import ChurnTrace, DeviceLeave
    from repro.data.pipeline import SyntheticImages
    model, tprof, tnet = _tree(m=4, e=2)
    fleet = api.Fleet.from_profile(tprof, tnet)
    data = SyntheticImages(model.input_shape, model.num_classes, 16,
                           seed=0)
    with pytest.raises(NotImplementedError, match="tree"):
        api.plan(model, fleet, 16).train(
            data, steps=2, churn=ChurnTrace((DeviceLeave(0, "device_0"),)))


def test_cloud_mesh_rejected_on_star_plan():
    from repro import api
    model = _tiny_mlp()
    star, _ = _api_fleets()
    with pytest.raises(ValueError, match="tree"):
        api.plan(model, star, 24).step_fn(cloud_mesh=object())
