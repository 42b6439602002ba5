"""SGD-equivalence of the hybrid-parallelism execution engine.

The paper's hybrid parallelism is a *distributed evaluation* of synchronous
SGD: any (m_s, m_l, b_o, b_s, b_l) schedule must produce exactly the update
of vanilla SGD on the concatenated batch.  We property-test that invariant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._compat import given, settings, st

from repro.core.cost_model import MultiSchedule, Schedule
from repro.core.hybrid_step import (hybrid_step_from_schedule,
                                    reference_sgd_step, traffic)
from repro.models.cnn import LayeredModel, ConvSpec, DenseSpec, lenet5

jax.config.update("jax_enable_x64", False)


def tiny_mlp(n_dense: int = 4, width: int = 16, num_classes: int = 5
             ) -> LayeredModel:
    specs = tuple(DenseSpec(f"fc{i}", width) for i in range(n_dense - 1)) + \
        (DenseSpec("out", num_classes, relu=False),)
    return LayeredModel("tiny_mlp", specs, (8,), num_classes)


def make_batch(key, model, B):
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (B,) + model.input_shape, jnp.float32)
    y = jax.random.randint(ky, (B,), 0, model.num_classes)
    return x, y


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000))
def test_hybrid_equals_reference_sgd(seed):
    rng = np.random.default_rng(seed)
    model = tiny_mlp()
    N = model.num_layers
    B = 12
    m_s = int(rng.integers(0, N + 1))
    m_l = int(rng.integers(m_s, N + 1))
    b_s = int(rng.integers(0, B)) if m_s > 0 else 0
    b_l = int(rng.integers(0, B - b_s)) if m_l > 0 else 0
    b_o = B - b_s - b_l
    sched = Schedule("cloud", "device", "edge", m_s, m_l, b_o, b_s, b_l)

    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    x, y = make_batch(key, model, B)
    lr = 0.05
    ref_params, ref_loss = reference_sgd_step(model, params, x, y, lr)
    hyb_params, hyb_loss = hybrid_step_from_schedule(
        model, params, x, y, MultiSchedule.from_schedule(sched), lr)

    assert hyb_loss == pytest.approx(float(ref_loss), rel=1e-5)
    for pr, ph in zip(ref_params, hyb_params):
        np.testing.assert_allclose(pr["w"], ph["w"], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(pr["b"], ph["b"], rtol=2e-5, atol=2e-6)


def test_hybrid_equals_reference_on_lenet():
    model = lenet5()
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    x, y = make_batch(key, model, 10)
    sched = Schedule("cloud", "device", "edge", 2, 3, 4, 3, 3)
    ref_params, _ = reference_sgd_step(model, params, x, y, 0.01)
    hyb_params, _ = hybrid_step_from_schedule(
        model, params, x, y, MultiSchedule.from_schedule(sched), 0.01)
    for pr, ph in zip(ref_params, hyb_params):
        np.testing.assert_allclose(pr["w"], ph["w"], rtol=5e-5, atol=1e-6)


def test_multi_step_training_descends_and_matches():
    """Several hybrid iterations == several reference iterations, and the
    loss goes down (end-to-end learning sanity)."""
    model = tiny_mlp()
    key = jax.random.PRNGKey(1)
    params_ref = model.init(key)
    params_hyb = [dict(p) for p in params_ref]
    sched = MultiSchedule.from_schedule(
        Schedule("edge", "device", "cloud", 1, 2, 4, 4, 4))
    losses = []
    for step in range(12):
        x, y = make_batch(jax.random.PRNGKey(100 + step), model, 12)
        params_ref, loss_ref = reference_sgd_step(model, params_ref, x, y,
                                                  0.05)
        params_hyb, loss_hyb = hybrid_step_from_schedule(
            model, params_hyb, x, y, sched, 0.05)
        assert float(loss_hyb) == pytest.approx(float(loss_ref), rel=1e-4)
        losses.append(float(loss_hyb))
    assert losses[-1] < losses[0]


def test_degenerate_schedules():
    """m_s = m_l = 0 (single worker) and m_s = m_l = N (full DP) both work."""
    model = tiny_mlp()
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    x, y = make_batch(key, model, 9)
    ref, _ = reference_sgd_step(model, params, x, y, 0.1)
    N = model.num_layers
    for sched in (Schedule("cloud", "device", "edge", 0, 0, 9, 0, 0),
                  Schedule("cloud", "device", "edge", N, N, 3, 3, 3)):
        hyb, _ = hybrid_step_from_schedule(
            model, params, x, y, MultiSchedule.from_schedule(sched), 0.1)
        for pr, ph in zip(ref, hyb):
            np.testing.assert_allclose(pr["w"], ph["w"], rtol=2e-5,
                                       atol=2e-6)


def test_traffic_matches_cost_model_datasizes():
    """Bytes moved by the hybrid step == the DataSize terms of Eq. (4)."""
    model = lenet5()
    metas = model.layer_meta()
    sched = Schedule("cloud", "device", "edge", 2, 3, 4, 3, 3)
    rep = traffic(model, sched, sample_bytes=3076.0)
    # input: b_o to cloud + b_l to edge (worker_s IS the device)
    assert rep.input_bytes == pytest.approx((4 + 3) * 3076.0)
    assert rep.activation_bytes == pytest.approx(
        2 * 3 * metas[1].out_bytes + 2 * 3 * metas[2].out_bytes)
    assert rep.weightgrad_bytes == pytest.approx(
        2 * sum(m.param_bytes for m in metas[:2]) +
        2 * sum(m.param_bytes for m in metas[:3]))
