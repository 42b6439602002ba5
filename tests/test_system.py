"""End-to-end system tests on 1 device: data -> train steps -> loss
decreases; hier CNN path end-to-end; microbatching semantics."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeSpec
from repro.data.pipeline import SyntheticImages, make_lm_batch_fn
from repro.models.lm.model import LMConfig, build_model
from repro.optim import get_optimizer
from repro.train.step import init_state, make_train_step


def test_lm_training_learns():
    cfg = LMConfig("sys", "dense", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, d_ff=128, vocab=128, dtype=jnp.float32)
    model = build_model(cfg)
    opt = get_optimizer("adamw", lr=3e-3, weight_decay=0.0)
    state = init_state(model, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, opt))
    shape = ShapeSpec("t", 64, 8, "train")
    fn = make_lm_batch_fn(cfg, shape, seed=0)
    losses = []
    for i in range(25):
        state, m = step(state, jax.tree.map(jnp.asarray, fn(i)),
                        jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]


def test_microbatched_step_matches_plain():
    """Gradient accumulation is semantics-preserving."""
    cfg = LMConfig("sys", "dense", n_layers=2, d_model=32, n_heads=2,
                   n_kv_heads=1, d_ff=64, vocab=64, dtype=jnp.float32)
    model = build_model(cfg)
    opt = get_optimizer("sgdm", lr=1e-2, clip_norm=0.0)
    s0 = init_state(model, opt, jax.random.PRNGKey(0))
    shape = ShapeSpec("t", 32, 8, "train")
    batch = jax.tree.map(jnp.asarray,
                         make_lm_batch_fn(cfg, shape, seed=0)(0))
    key = jax.random.PRNGKey(0)
    s1, m1 = make_train_step(model, opt, microbatches=1)(s0, batch, key)
    s4, m4 = make_train_step(model, opt, microbatches=4)(s0, batch, key)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s4["params"])):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_hier_cnn_end_to_end():
    from repro.core.cost_model import Network
    from repro.core.cost_model import MultiSchedule
    from repro.core.hybrid_step import hybrid_step_from_schedule
    from repro.core.profiler import analytic_profile
    from repro.core.scheduler import solve
    from repro.models.cnn import lenet5

    model = lenet5()
    profile = analytic_profile(model)
    net = Network(bw_de=5e6 / 8, bw_ec=2e6 / 8)
    sched = MultiSchedule.from_schedule(solve(profile, net, 32).schedule)
    data = SyntheticImages(model.input_shape, model.num_classes, 32,
                           seed=0)
    params = model.init(jax.random.PRNGKey(0))
    losses = []
    for i in range(20):
        b = data.batch(i)
        params, loss = hybrid_step_from_schedule(
            model, params, jnp.asarray(b["x"]), jnp.asarray(b["labels"]),
            sched, lr=0.05)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
