"""M-device generalization (DESIGN.md §6): equivalence and validity suite.

Four invariant families:

* **M=1 exactness** — the generalized cost model, scheduler and execution
  engine must reproduce the three-worker path *bit-for-bit* (same
  schedules, same ``T_total``, identical parameter updates) across the
  Table II profiles and the paper-calibrated testbeds.
* **Backend equivalence** — ``solve_multi(backend="batched")`` equals the
  scalar-LP reference oracle for M >= 2, and pruning/refinement never make
  the answer worse.
* **Rounding invariants** — the M+2-wide sample-split rounding conserves
  the batch, never drives any ``b_i`` negative, and pins disallowed
  entries to zero (property-tested via the ``tests/_compat`` shim).
* **Model validity at M > 1** — the DES makespan matches the generalized
  Eq. 12 within the Fig.-6 tolerance, and the M-stream hybrid step is
  exact batch-B SGD.
"""
import itertools

import numpy as np
import pytest
from tests._compat import given, settings, st

from repro.core.cost_model import (HierProfile, MultiProfile, MultiSchedule,
                                   Network, Schedule, StarNetwork, WIDX,
                                   WORKERS, t_total, t_total_batch,
                                   t_total_multi, t_total_multi_batch)
from repro.core.scheduler import (_round_batch_split_batch, solve,
                                  solve_multi)

MBPS = 1e6 / 8.0

# Table II synthetic profiles (same construction as
# benchmarks/table2_sched_runtime.synthetic_profile).
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


def hetero_profile(n: int, scales, seed: int = 1) -> MultiProfile:
    return MultiProfile.from_hier(synthetic_profile(n), scales)


def hetero_net(m: int, seed: int = 0) -> StarNetwork:
    rng = np.random.default_rng(seed)
    return StarNetwork(bw_de=rng.uniform(2.0, 5.0, m) * MBPS,
                       bw_ec=3.0 * MBPS)


# ---------------------------------------------------------------------------
# M=1 exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,n", sorted(TABLE2_LAYERS.items()))
@pytest.mark.parametrize("ec_mbps", [2.0, 3.5])
def test_m1_scheduler_bit_identical_to_three_worker(name, n, ec_mbps):
    """The generalized scheduler at M=1 *is* Algorithm 1: same schedule,
    same T_total, same candidate/prune counts, across Table II profiles."""
    prof = synthetic_profile(n)
    net = Network(bw_de=5.0 * MBPS, bw_ec=ec_mbps * MBPS)
    r3 = solve(prof, net, B=64)
    rm = solve_multi(MultiProfile.from_hier(prof, (1.0,)),
                     StarNetwork.from_network(net, 1), B=64)
    assert rm.schedule.to_schedule() == r3.schedule
    assert rm.t_total == r3.t_total          # bit-for-bit, not approx
    assert rm.n_candidates == r3.n_candidates
    assert rm.n_pruned == r3.n_pruned
    assert rm.refine_rounds == 0


def test_m1_reference_backend_bit_identical():
    prof = synthetic_profile(6)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    r3 = solve(prof, net, B=48, backend="reference")
    rm = solve_multi(MultiProfile.from_hier(prof, (1.0,)),
                     StarNetwork.from_network(net, 1), B=48,
                     backend="reference")
    assert rm.schedule.to_schedule() == r3.schedule
    assert rm.t_total == r3.t_total


def test_m1_cost_model_bitwise_equal_on_every_mapping_and_cut():
    prof = synthetic_profile(5)
    net = Network(bw_de=5.0 * MBPS, bw_ec=3.0 * MBPS)
    mprof = MultiProfile.from_hier(prof, (1.0,))
    mnet = StarNetwork.from_network(net, 1)
    n = prof.num_layers
    rng = np.random.default_rng(7)
    for wo, ws, wl in itertools.permutations(WORKERS, 3):
        for m_s in range(n + 1):
            for m_l in range(m_s, n + 1):
                b = rng.multinomial(32, [1 / 3] * 3)
                bo, bs, bl = (int(v) for v in b)
                if m_s == 0:
                    bo, bs = bo + bs, 0
                if m_l == 0:
                    bo, bl = bo + bl, 0
                sched = Schedule(wo, ws, wl, m_s, m_l, bo, bs, bl)
                ref = t_total(prof, net, sched)
                got = t_total_multi(mprof, mnet,
                                    MultiSchedule.from_schedule(sched))
                assert got.total == ref.total
                assert got.t_f1 == ref.t_f1 and got.t_b2 == ref.t_b2
                assert got.t_update == ref.t_update
                # and the batched kernel agrees with both
                tb = t_total_multi_batch(
                    mprof, mnet, np.array([WIDX[wo]]),
                    np.array([[WIDX[ws]]]), np.array([WIDX[wl]]),
                    np.array([[m_s]]), np.array([m_l]),
                    np.array([[bo, bs, bl]]))
                t3 = t_total_batch(prof, net, np.array([WIDX[wo]]),
                                   np.array([WIDX[ws]]),
                                   np.array([WIDX[wl]]), np.array([m_s]),
                                   np.array([m_l]),
                                   np.array([[bo, bs, bl]]))
                assert tb[0] == ref.total == t3[0]


# ---------------------------------------------------------------------------
# Backend equivalence and search-quality invariants (M >= 2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,scales", [(2, (1.0, 1.7)),
                                      (3, (1.0, 1.4, 2.3))])
def test_multi_batched_equals_reference(m, scales):
    prof = hetero_profile(5, scales)
    net = hetero_net(m)
    rb = solve_multi(prof, net, B=48)
    rr = solve_multi(prof, net, B=48, backend="reference")
    assert rb.schedule == rr.schedule
    assert rb.t_total == rr.t_total


def test_multi_pruning_never_changes_the_answer():
    prof = hetero_profile(6, (1.0, 1.9))
    net = hetero_net(2, seed=3)
    a = solve_multi(prof, net, B=64, prune=True)
    b = solve_multi(prof, net, B=64, prune=False)
    assert a.t_total == b.t_total
    assert a.n_pruned > 0 or a.n_candidates == a.n_lp_solved


def test_multi_refinement_never_worse_and_cuts_stay_feasible():
    prof = hetero_profile(6, (1.0, 1.5, 2.0, 2.8))
    net = hetero_net(4, seed=5)
    base = solve_multi(prof, net, B=96, refine_passes=0)
    ref = solve_multi(prof, net, B=96)
    assert ref.t_total <= base.t_total
    s = ref.schedule
    assert all(0 <= mi <= s.m_l for mi in s.m_s)
    assert s.b_o + sum(s.b_s) + s.b_l == 96
    assert all(b >= 0 for b in (s.b_o, *s.b_s, s.b_l))


def test_multi_never_worse_than_all_edge_or_all_cloud():
    for m, scales in ((2, (1.0, 1.6)), (4, (1.0, 1.3, 1.9, 2.6))):
        prof = hetero_profile(6, scales)
        net = hetero_net(m, seed=m)
        res = solve_multi(prof, net, B=64)
        for owner in ("edge", "cloud"):
            other = "cloud" if owner == "edge" else "edge"
            triv = MultiSchedule(
                worker_o=owner, worker_l=other,
                s_workers=prof.device_names, m_s=(0,) * m, m_l=0,
                b_o=64, b_s=(0,) * m, b_l=0)
            assert res.t_total <= t_total_multi(prof, net, triv).total + 1e-12


# ---------------------------------------------------------------------------
# Sample-split rounding at M+2 width (ISSUE: conserve the batch, never
# drive any b_i negative, disallowed entries pinned to zero)
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.integers(0, 100_000))
def test_multi_rounding_conserves_batch_and_nonneg(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 9))          # devices; width = m + 2
    width = m + 2
    B = int(rng.integers(1, 129))
    K = int(rng.integers(1, 6))
    allowed = rng.random((K, width)) < 0.7
    allowed[:, 0] = True                 # b_o always allowed
    b = rng.dirichlet(np.ones(width), size=K) * B
    b += rng.normal(0, 0.4, (K, width))  # exercise deficit and overshoot
    out = _round_batch_split_batch(b, B, allowed)
    assert (out.sum(axis=1) == B).all()
    assert (out >= 0).all()
    assert (out[~allowed] == 0).all()


# ---------------------------------------------------------------------------
# Model validity at M > 1: DES vs generalized Eq. 12
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [2, 4])
def test_multi_simulator_matches_cost_model(m):
    from benchmarks.common import fleet_profile, star_network
    from repro.core.simulator import simulate_iteration_multi
    prof = fleet_profile("lenet5", m)
    net = star_network(m, 3.0)
    res = solve_multi(prof, net, B=128)
    sim = simulate_iteration_multi(prof, net, res.schedule)
    rel = abs(sim - res.t_total) / res.t_total
    assert rel < 0.25, (sim, res.t_total)   # Fig.-6 tolerance


def test_multi_simulator_cloud_ingest_within_tolerance():
    """All-Cloud-style schedules upload the whole batch through the shared
    backhaul; the DES must serialize the M input flows there (not give
    each its own bw_ec share) to stay within the Fig.-6 tolerance."""
    from benchmarks.common import fleet_profile, star_network
    from repro.core.simulator import simulate_iteration_multi
    for m in (2, 4):
        prof = fleet_profile("lenet5", m)
        net = star_network(m, 3.0)
        sched = MultiSchedule(
            worker_o="cloud", worker_l="edge", s_workers=prof.device_names,
            m_s=(0,) * m, m_l=0, b_o=64, b_s=(0,) * m, b_l=0)
        want = t_total_multi(prof, net, sched).total
        sim = simulate_iteration_multi(prof, net, sched)
        assert abs(sim - want) / want < 0.25, (m, sim, want)


def test_multi_simulator_m1_matches_three_worker_sim_on_local_schedules():
    """On schedules with no input upload for o/l the per-class input pipes
    are inert, so the M=1 multi DES must equal the 3-worker DES exactly."""
    from repro.core.simulator import (simulate_iteration,
                                      simulate_iteration_multi)
    prof = synthetic_profile(5)
    net = Network(bw_de=4.0 * MBPS, bw_ec=2.0 * MBPS)
    sched = Schedule("device", "edge", "cloud", 2, 4, 10, 12, 10)
    got = simulate_iteration_multi(MultiProfile.from_hier(prof, (1.0,)),
                                   StarNetwork.from_network(net, 1),
                                   MultiSchedule.from_schedule(sched))
    want = simulate_iteration(prof, net, sched)
    assert got == pytest.approx(want, rel=1e-12)


# ---------------------------------------------------------------------------
# M-stream execution engine: exact SGD semantics
# ---------------------------------------------------------------------------

def _tiny_mlp():
    from repro.models.cnn import DenseSpec, LayeredModel
    specs = tuple(DenseSpec(f"fc{i}", 16) for i in range(4)) + \
        (DenseSpec("out", 5, relu=False),)
    return LayeredModel("tiny_mlp", specs, (8,), 5)


def _batch(model, B, seed=0):
    import jax
    import jax.numpy as jnp
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (B,) + model.input_shape, jnp.float32)
    y = jax.random.randint(ky, (B,), 0, model.num_classes)
    return x, y


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_multi_stream_step_equals_reference_sgd(seed):
    import jax
    from repro.core.hybrid_step import (hybrid_step_from_schedule,
                                        reference_sgd_step)
    rng = np.random.default_rng(seed)
    model = _tiny_mlp()
    N = model.num_layers
    M = int(rng.integers(2, 5))
    B = 16
    m_l = int(rng.integers(0, N + 1))
    m_s = tuple(int(rng.integers(0, m_l + 1)) for _ in range(M))
    splits = rng.multinomial(B, np.ones(M + 2) / (M + 2))
    b_s = [int(v) if m_s[i] > 0 else 0 for i, v in enumerate(splits[1:1 + M])]
    b_l = int(splits[1 + M]) if m_l > 0 else 0
    b_o = B - sum(b_s) - b_l
    names = tuple(f"device_{i}" for i in range(M)) + ("edge", "cloud")
    order = rng.permutation(M + 2)
    sched = MultiSchedule(
        worker_o=names[order[0]], worker_l=names[order[1]],
        s_workers=tuple(names[i] for i in order[2:]),
        m_s=m_s, m_l=m_l, b_o=b_o, b_s=tuple(b_s), b_l=b_l)
    x, y = _batch(model, B, seed)
    params = model.init(jax.random.PRNGKey(seed))
    hyb, _ = hybrid_step_from_schedule(model, params, x, y, sched,
                                       lr=0.05)
    ref, _ = reference_sgd_step(model, params, x, y, 0.05)
    for pr, ph in zip(ref, hyb):
        np.testing.assert_allclose(pr["w"], ph["w"], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(pr["b"], ph["b"], rtol=2e-5, atol=2e-6)


def test_star_m1_step_bit_identical_to_triple_step():
    """The triple fleet and the M=1 star fleet over the same profile,
    pinned to one split schedule, give bit-identical params and loss
    through ``Plan.step_fn``."""
    import dataclasses

    import jax
    from repro.api import Fleet, plan
    model = _tiny_mlp()
    sched = Schedule("device", "edge", "cloud", 2, 4, 6, 5, 5)
    x, y = _batch(model, 16, seed=3)
    outs = []
    for topology, s in (("triple", sched),
                        ("star", MultiSchedule.from_schedule(sched))):
        p = plan(model, Fleet.from_table2(topology=topology), 16)
        assert p.fleet.topology == topology
        p = dataclasses.replace(
            p, result=dataclasses.replace(p.result, schedule=s))
        outs.append(p.step_fn(lr=0.05)(model.init(jax.random.PRNGKey(3)),
                                       x, y))
    (p3, l3), (pm, lm) = outs
    assert float(l3) == float(lm)
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(pm)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_run_multi_hier_loop_straggler_resched():
    """Online re-scheduling sheds load from an injected straggler device."""
    import jax
    from repro.core.profiler import multi_analytic_profile
    from repro.data.pipeline import SyntheticImages
    from repro.train.loop import HierLoopConfig, run_multi_hier_loop

    model = _tiny_mlp()
    prof = multi_analytic_profile(model, device_slowdowns=(1.0, 1.2))
    net = StarNetwork(bw_de=np.array([4.0, 3.0]) * MBPS, bw_ec=2.0 * MBPS)
    data = SyntheticImages(model.input_shape, model.num_classes, 24, seed=0)

    def slowdown(step):
        return {"device_1": 40.0} if step >= 4 else {}

    cfg = HierLoopConfig(total_steps=10, batch=24, resched_every=4)
    out = run_multi_hier_loop(cfg, model, prof, net, data,
                              worker_slowdown=slowdown)
    assert len(out["history"]) == 10
    assert np.isfinite(out["history"][-1]["loss"])
    assert out["wall"] > 0
    final = out["final_schedule"]
    assert final.batch == 24
