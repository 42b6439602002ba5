"""Adapter-equivalence suite (DESIGN.md §8).

The CNN path through the :class:`LayerStack` protocol must be **bitwise**
identical to the legacy ``LayeredModel`` path: profiles, schedules,
``t_total`` and trained params all ``==``.  Plus the explicit-``MG``
(backward wire bytes) channel and the bounded jit-step LRU.
"""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hybrid_step as hs
from repro.core.cost_model import (HierProfile, MultiSchedule, Network,
                                   Schedule, StarNetwork, t_total,
                                   t_total_batch, t_total_multi,
                                   t_total_multi_batch)
from repro.core.layerstack import CnnLayerStack, CutMeta, as_layerstack
from repro.core.pipeline import t_period, t_period_batch
from repro.core.profiler import (ALEXNET_TESTBED, PAPER_TESTBED,
                                 analytic_profile, multi_analytic_profile)
from repro.core.scheduler import solve, solve_multi
from repro.core.simulator import simulate_iteration
from repro.models.cnn import DenseSpec, LayeredModel, alexnet, lenet5

jax.config.update("jax_enable_x64", False)

TABLE2 = [(lenet5, PAPER_TESTBED), (alexnet, ALEXNET_TESTBED)]


def tiny_mlp(n_dense: int = 4, width: int = 16, num_classes: int = 5
             ) -> LayeredModel:
    specs = tuple(DenseSpec(f"fc{i}", width) for i in range(n_dense - 1)) + \
        (DenseSpec("out", num_classes, relu=False),)
    return LayeredModel("tiny_mlp", specs, (8,), num_classes)


# ---------------------------------------------------------------------------
# CNN-via-LayerStack == legacy path, bitwise.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("build,testbed", TABLE2)
def test_cnn_adapter_profile_bitwise(build, testbed):
    model = build()
    p_legacy = analytic_profile(model, testbed)
    p_adapter = analytic_profile(CnnLayerStack(model), testbed)
    assert p_legacy.layer_names == p_adapter.layer_names
    for f in ("L_f", "L_b", "L_u", "MP", "MO", "MG"):
        assert (getattr(p_legacy, f) == getattr(p_adapter, f)).all(), f
    assert p_legacy.sample_bytes == p_adapter.sample_bytes
    # grad_bytes defaults to act_bytes on the CNN path.
    assert (p_legacy.MG == p_legacy.MO).all()


@pytest.mark.parametrize("build,testbed", TABLE2)
@pytest.mark.parametrize("ec_mbps", [1.5, 5.0])
def test_cnn_adapter_schedule_and_t_total_bitwise(build, testbed, ec_mbps):
    model = build()
    net = Network(bw_de=5e6 / 8, bw_ec=ec_mbps * 1e6 / 8)
    r_legacy = solve(analytic_profile(model, testbed), net, 32)
    r_adapter = solve(analytic_profile(CnnLayerStack(model), testbed),
                      net, 32)
    assert r_legacy.schedule == r_adapter.schedule
    assert r_legacy.t_total == r_adapter.t_total
    assert r_legacy.t_period == r_adapter.t_period


@pytest.mark.parametrize("seed,sched", [
    (0, MultiSchedule.from_schedule(
        Schedule("cloud", "device", "edge", 2, 3, 5, 4, 3))),
    (3, MultiSchedule(worker_o="edge", worker_l="cloud",
                      s_workers=("device_0", "device_1"), m_s=(1, 2),
                      m_l=3, b_o=3, b_s=(2, 3), b_l=2)),
], ids=["triple", "two_streams"])
def test_cnn_adapter_trained_params_bitwise(seed, sched):
    model = tiny_mlp()
    stack = as_layerstack(model)
    assert isinstance(stack, CnnLayerStack)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    x = jax.random.normal(key, (sched.batch,) + model.input_shape,
                          jnp.float32)
    y = jax.random.randint(key, (sched.batch,), 0, model.num_classes)
    p1, l1 = hs.hybrid_step_from_schedule(model, params, x, y, sched, 0.05)
    p2, l2 = hs.hybrid_step_from_schedule(stack, params, x, y, sched, 0.05)
    assert float(l1) == float(l2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert (np.asarray(a) == np.asarray(b)).all()
    # reference step too
    r1, _ = hs.reference_sgd_step(model, params, x, y, 0.05)
    r2, _ = hs.reference_sgd_step(stack, params, x, y, 0.05)
    for a, b in zip(jax.tree.leaves(r1), jax.tree.leaves(r2)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_as_layerstack_rejects_unknown():
    with pytest.raises(TypeError):
        as_layerstack(object())


def test_cut_meta_defaults():
    m = CutMeta(name="x", param_count=10, flops_fwd=100.0, act_bytes=64.0)
    assert m.resolved_param_bytes == 40.0
    assert m.resolved_grad_bytes == 64.0
    e = CutMeta(name="y", param_count=10, flops_fwd=100.0, act_bytes=64.0,
                grad_bytes=128.0, param_bytes=20.0)
    assert e.resolved_param_bytes == 20.0
    assert e.resolved_grad_bytes == 128.0


# ---------------------------------------------------------------------------
# Explicit backward wire bytes (MG).
# ---------------------------------------------------------------------------


def _mg_profile(scale: float | None) -> HierProfile:
    prof = analytic_profile(lenet5(), PAPER_TESTBED)
    mg = None if scale is None else prof.MO * scale
    return HierProfile(layer_names=prof.layer_names, L_f=prof.L_f,
                       L_b=prof.L_b, L_u=prof.L_u, MP=prof.MP, MO=prof.MO,
                       sample_bytes=prof.sample_bytes, MG=mg)


def test_mg_defaults_to_mo_bitwise():
    net = Network(bw_de=5e6 / 8, bw_ec=2.5e6 / 8)
    sched = Schedule("cloud", "device", "edge", 2, 3, 10, 12, 10)
    p_default = _mg_profile(None)
    p_explicit = _mg_profile(1.0)
    assert (p_default.MG == p_default.MO).all()
    bd0 = t_total(p_default, net, sched)
    bd1 = t_total(p_explicit, net, sched)
    assert bd0.total == bd1.total
    assert bd0.comm_activation == bd1.comm_activation
    assert t_period(p_default, net, sched) == t_period(p_explicit, net,
                                                       sched)
    assert simulate_iteration(p_default, net, sched) == \
        simulate_iteration(p_explicit, net, sched)


def test_mg_raises_backward_comm_only():
    net = Network(bw_de=5e6 / 8, bw_ec=2.5e6 / 8)
    sched = Schedule("cloud", "device", "edge", 2, 3, 10, 12, 10)
    bd0 = t_total(_mg_profile(None), net, sched)
    bd2 = t_total(_mg_profile(2.0), net, sched)
    # forward phase untouched; backward phases can only grow.
    assert bd2.t_f1 == bd0.t_f1 and bd2.t_f2 == bd0.t_f2
    assert bd2.t_b1 >= bd0.t_b1 and bd2.t_b2 >= bd0.t_b2
    assert bd2.total > bd0.total
    # comm_activation = forward + backward legs: doubling MG adds exactly
    # the backward half again.
    assert bd2.comm_activation == pytest.approx(1.5 * bd0.comm_activation)


def test_mg_scalar_batch_agree_and_backends_agree():
    prof = _mg_profile(2.0)
    net = Network(bw_de=5e6 / 8, bw_ec=2.5e6 / 8)
    scheds = [Schedule("cloud", "device", "edge", 2, 3, 10, 12, 10),
              Schedule("edge", "device", "cloud", 1, 4, 8, 16, 8),
              Schedule("device", "edge", "cloud", 0, 5, 20, 0, 12)]
    for sched in scheds:
        o = np.array([{"device": 0, "edge": 1, "cloud": 2}[sched.worker_o]])
        s = np.array([{"device": 0, "edge": 1, "cloud": 2}[sched.worker_s]])
        l = np.array([{"device": 0, "edge": 1, "cloud": 2}[sched.worker_l]])
        ms, ml = np.array([sched.m_s]), np.array([sched.m_l])
        b = np.array([[sched.b_o, sched.b_s, sched.b_l]])
        assert t_total_batch(prof, net, o, s, l, ms, ml, b)[0] == \
            t_total(prof, net, sched).total
        assert t_period_batch(prof, net, o, s, l, ms, ml, b)[0] == \
            t_period(prof, net, sched)
    # the full solver agrees across backends with a non-trivial MG.
    r_b = solve(prof, net, 32, backend="batched")
    r_r = solve(prof, net, 32, backend="reference")
    assert r_b.t_total == r_r.t_total


def test_mg_multi_m1_bitwise_and_solver():
    from repro.core.cost_model import MultiProfile
    prof3 = _mg_profile(2.0)
    net3 = Network(bw_de=5e6 / 8, bw_ec=2.5e6 / 8)
    prof = MultiProfile.from_hier(prof3)
    assert (prof.MG == prof3.MG).all()
    net = StarNetwork.from_network(net3)
    sched3 = Schedule("cloud", "device", "edge", 2, 3, 10, 12, 10)
    sched = MultiSchedule.from_schedule(sched3)
    assert t_total_multi(prof, net, sched).total == \
        t_total(prof3, net3, sched3).total
    widx = prof.widx
    o = np.array([widx[sched.worker_o]])
    s = np.array([[widx[w] for w in sched.s_workers]])
    l = np.array([widx[sched.worker_l]])
    ms, ml = np.array([list(sched.m_s)]), np.array([sched.m_l])
    b = np.array([[sched.b_o, *sched.b_s, sched.b_l]])
    assert t_total_multi_batch(prof, net, o, s, l, ms, ml, b)[0] == \
        t_total_multi(prof, net, sched).total
    r1 = solve_multi(prof, net, 32)
    r3 = solve(prof3, net3, 32)
    assert r1.t_total == r3.t_total


def test_multi_profile_from_hier_carries_mg():
    prof = multi_analytic_profile(lenet5(), PAPER_TESTBED,
                                  device_slowdowns=(1.0, 1.5))
    assert (prof.MG == prof.MO).all()


# ---------------------------------------------------------------------------
# Bounded jit-step LRU.
# ---------------------------------------------------------------------------


def _fresh_cache(maxsize):
    cache = hs._JitStepCache(maxsize=maxsize)
    return cache


def test_jit_cache_is_bounded_and_evicts_lru(monkeypatch):
    monkeypatch.setattr(hs, "_JIT_CACHE", _fresh_cache(3))
    model = tiny_mlp()
    fns = [hs.jitted_hybrid_step(model, (m,), m, 0.1) for m in range(3)]
    assert len(hs._JIT_CACHE) == 3
    # hit: same (model, cuts, lr) returns the cached callable
    assert hs.jitted_hybrid_step(model, (0,), 0, 0.1) is fns[0]
    # inserting a 4th evicts the least-recently-used entry (m=1: the m=0
    # entry was just touched)
    hs.jitted_hybrid_step(model, (3,), 3, 0.1)
    assert len(hs._JIT_CACHE) == 3
    assert ("step", id(model), (1,), 1, 0.1, "none", (0,), None) \
        not in hs._JIT_CACHE
    assert ("step", id(model), (0,), 0, 0.1, "none", (0,), None) \
        in hs._JIT_CACHE


def test_jit_cache_releases_model_on_eviction(monkeypatch):
    monkeypatch.setattr(hs, "_JIT_CACHE", _fresh_cache(2))
    model = tiny_mlp()
    ref = weakref.ref(model)
    hs.jitted_hybrid_step(model, (1,), 1, 0.1)
    del model
    gc.collect()
    # pinned while cached: the id-keyed handle stays valid
    assert ref() is not None
    # filling the cache with other models evicts the entry -> collectable
    keep = [tiny_mlp(width=8), tiny_mlp(width=12)]
    for m in keep:
        hs.jitted_hybrid_step(m, (1,), 1, 0.1)
    gc.collect()
    assert ref() is None, "evicted model must be garbage-collectable"


def test_jit_cache_still_caches_across_reschedules(monkeypatch):
    monkeypatch.setattr(hs, "_JIT_CACHE", _fresh_cache(8))
    model = tiny_mlp()
    f1 = hs.jitted_hybrid_step(model, (1,), 2, 0.1)
    f2 = hs.jitted_hybrid_step(model, (2,), 3, 0.1)
    assert f1 is not f2
    assert hs.jitted_hybrid_step(model, (1,), 2, 0.1) is f1
    # the explicit all-zero stream→edge map is the default's entry
    assert hs.jitted_hybrid_step(model, (1,), 2, 0.1,
                                 stream_edge=(0,)) is f1
    g1 = hs.jitted_hybrid_step(model, (1, 1), 2, 0.1)
    assert hs.jitted_hybrid_step(model, (1, 1), 2, 0.1) is g1
    r1 = hs.jitted_reference_step(model, 0.1)
    assert hs.jitted_reference_step(model, 0.1) is r1
    assert len(hs._JIT_CACHE) == 4
    hs._JIT_CACHE.clear()
    assert len(hs._JIT_CACHE) == 0
