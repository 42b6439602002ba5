"""LM model-zoo LayerStack: 4 block families end-to-end on the HierTrain
core (DESIGN.md §8) — solve -> hybrid step -> simulate, hybrid exactness
vs the reference SGD step at several cuts, analytic meta pinned to the
real init shapes, and the HLO FLOP cross-check.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import Network, Schedule, StarNetwork
from repro.core.hybrid_step import (hybrid_step_from_schedule,
                                    multi_hybrid_step_from_schedule,
                                    reference_sgd_step)
from repro.core.profiler import LM_TESTBED, analytic_profile, \
    multi_analytic_profile
from repro.core.scheduler import solve, solve_multi
from repro.core.simulator import simulate_iteration, simulate_pipeline
from repro.models.lm.layerstack import (FAMILY_LABELS, hlo_crosscheck_flops,
                                        lm_layerstack)
from repro.models.lm.model import LMConfig
from repro.models.lm.moe import MoEConfig
from repro.models.lm.ssm import SSMConfig
from repro.models.lm.xlstm import XLSTMConfig

jax.config.update("jax_enable_x64", False)

T = 16
B = 8

# f32 tiny configs: tight numeric tolerances, fast CPU compiles.
CFGS = {
    "attention": LMConfig(
        name="tiny-attn", family="dense", n_layers=4, d_model=32, n_heads=4,
        n_kv_heads=2, d_ff=64, vocab=97, dtype=jnp.float32, remat=False),
    "moe": LMConfig(
        name="tiny-moe", family="moe", n_layers=3, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab=97,
        # capacity_factor = n_experts => capacity is lossless (no token is
        # ever dropped), which is what makes the routed forward exactly
        # decomposable across the hybrid batch split (see
        # models/lm/layerstack.py MoE caveat).
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                      group_size=4096, capacity_factor=4.0),
        dtype=jnp.float32, remat=False),
    "gla": LMConfig(
        name="tiny-gla", family="zamba", n_layers=4, d_model=32, n_heads=4,
        n_kv_heads=4, d_ff=64, vocab=97,
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8),
        shared_attn_every=2, dtype=jnp.float32, remat=False),
    "xlstm": LMConfig(
        name="tiny-xlstm", family="xlstm", n_layers=4, d_model=32,
        n_heads=4, n_kv_heads=4, d_ff=64, vocab=97,
        xlstm=XLSTMConfig(n_heads=2, slstm_every=2, chunk=8),
        dtype=jnp.float32, remat=False),
}
FAMILIES = sorted(CFGS)
NET = Network(bw_de=5e6 / 8, bw_ec=2.5e6 / 8)


def _stack(family):
    return lm_layerstack(CFGS[family], seq_len=T)


@pytest.mark.parametrize("family", FAMILIES)
def test_meta_param_counts_match_init(family):
    stack = _stack(family)
    params = stack.init(jax.random.PRNGKey(0))
    actual = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(p))
              for p in params]
    metas = stack.cut_meta()
    assert [m.param_count for m in metas] == actual
    assert metas[0].name == "embed" and metas[-1].name == "head"
    assert stack.num_layers == len(params)
    assert stack.family == FAMILY_LABELS[CFGS[family].family]


@pytest.mark.parametrize("family", FAMILIES)
def test_hybrid_exact_vs_reference_at_several_cuts(family):
    stack = _stack(family)
    N = stack.num_layers
    key = jax.random.PRNGKey(1)
    params = stack.init(key)
    x, y = stack.dummy_batch(key, B)
    lr = 0.05
    ref_params, ref_loss = reference_sgd_step(stack, params, x, y, lr)
    cuts = [(0, 0), (1, 2), (2, N - 1), (N, N)]
    for m_s, m_l in cuts:
        b_s = 3 if m_s > 0 else 0
        b_l = 2 if m_l > 0 else 0
        sched = Schedule("cloud", "device", "edge", m_s, m_l,
                         B - b_s - b_l, b_s, b_l)
        hyb_params, hyb_loss = hybrid_step_from_schedule(
            stack, params, x, y, sched, lr)
        assert float(hyb_loss) == pytest.approx(float(ref_loss), rel=1e-5)
        for a, b in zip(jax.tree.leaves(ref_params),
                        jax.tree.leaves(hyb_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_multi_hybrid_exact_two_streams():
    stack = _stack("attention")
    from repro.core.cost_model import MultiSchedule
    key = jax.random.PRNGKey(2)
    params = stack.init(key)
    x, y = stack.dummy_batch(key, 9)
    sched = MultiSchedule(worker_o="edge", worker_l="cloud",
                          s_workers=("device_0", "device_1"), m_s=(1, 2),
                          m_l=4, b_o=3, b_s=(2, 2), b_l=2)
    ref_params, ref_loss = reference_sgd_step(stack, params, x, y, 0.05)
    hyb_params, hyb_loss = multi_hybrid_step_from_schedule(
        stack, params, x, y, sched, 0.05)
    assert float(hyb_loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(ref_params),
                    jax.tree.leaves(hyb_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("family", FAMILIES)
def test_solve_step_simulate_end_to_end(family):
    """The ISSUE acceptance path: schedule -> execute -> simulate."""
    stack = _stack(family)
    prof = analytic_profile(stack, LM_TESTBED)
    # bf16-free: f32 configs => MG == MO on hidden cuts
    for objective in ("latency", "throughput"):
        res = solve(prof, NET, B, objective=objective)
        assert np.isfinite(res.t_total) and res.t_total > 0
        sim = simulate_iteration(prof, NET, res.schedule)
        assert np.isfinite(sim) and sim > 0
        assert simulate_pipeline(prof, NET, res.schedule, K=1) == sim
    key = jax.random.PRNGKey(3)
    params = stack.init(key)
    x, y = stack.dummy_batch(key, B)
    new_params, loss = hybrid_step_from_schedule(
        stack, params, x, y, res.schedule, 0.05)
    assert np.isfinite(float(loss))
    assert len(new_params) == stack.num_layers


@pytest.mark.parametrize("family", ["attention", "gla"])
def test_solve_multi_fleet(family):
    stack = _stack(family)
    prof = multi_analytic_profile(stack, LM_TESTBED,
                                  device_slowdowns=(1.0, 1.6))
    net = StarNetwork(bw_de=np.array([5e6 / 8, 4e6 / 8]), bw_ec=2.5e6 / 8)
    res = solve_multi(prof, net, B)
    assert np.isfinite(res.t_total) and res.t_total > 0
    assert len(res.schedule.s_workers) == 2
    from repro.core.simulator import simulate_iteration_multi
    sim = simulate_iteration_multi(prof, net, res.schedule)
    assert np.isfinite(sim) and sim > 0


def test_bf16_profile_sets_grad_bytes_wider_than_act_bytes():
    cfg = CFGS["attention"].variant(dtype=jnp.bfloat16)
    stack = lm_layerstack(cfg, seq_len=T)
    prof = analytic_profile(stack, LM_TESTBED)
    # hidden cuts ship bf16 forward / f32 back: MG == 2 * MO
    assert (prof.MG == 2.0 * prof.MO).all()
    # the solver consumes the asymmetric profile fine
    res = solve(prof, NET, B)
    assert np.isfinite(res.t_total)


def test_run_hier_loop_on_lm_stack():
    stack = _stack("attention")
    prof = analytic_profile(stack, LM_TESTBED)

    class Data:
        def batch(self, step):
            x, y = stack.dummy_batch(jax.random.PRNGKey(100 + step), B)
            return {"x": x, "labels": y}

    from repro.train.loop import HierLoopConfig, run_hier_loop
    cfg = HierLoopConfig(total_steps=4, batch=B, lr=0.05)
    out = run_hier_loop(cfg, stack, prof, NET, Data())
    assert len(out["history"]) == 4
    assert all(np.isfinite(h["loss"]) for h in out["history"])
    assert out["wall"] > 0


def test_unsupported_families_rejected():
    enc = LMConfig(name="enc", family="encdec", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=4, d_ff=64, vocab=97,
                   encoder_layers=2, dtype=jnp.float32)
    with pytest.raises(ValueError):
        lm_layerstack(enc, seq_len=T)
    vlm = CFGS["attention"].variant(n_frontend_tokens=4)
    with pytest.raises(ValueError):
        lm_layerstack(vlm, seq_len=T)


@pytest.mark.parametrize("family,cut,lo,hi", [
    ("attention", 1, 0.95, 1.05),    # pure dense matmuls: near-exact
    ("gla", 1, 0.9, 1.1),            # mamba2 (chunked GLA) block
    ("gla", 2, 0.9, 1.1),            # hybrid: shared block + mamba2
    ("xlstm", 1, 0.9, 1.1),          # mLSTM block
    ("moe", 1, 0.6, 1.4),            # capacity-dependent dispatch einsums
])
def test_hlo_crosscheck_block_flops(family, cut, lo, hi):
    """Analytic per-block FLOPs vs launch/hlo_analysis.loop_aware_cost on
    the compiled segment."""
    stack = _stack(family)
    analytic, measured = hlo_crosscheck_flops(stack, cut, batch=2)
    assert measured > 0
    assert lo <= analytic / measured <= hi, (analytic, measured)


def test_hlo_crosscheck_head_exact():
    stack = _stack("attention")
    analytic, measured = hlo_crosscheck_flops(stack, stack.num_layers - 1,
                                              batch=2)
    assert analytic == pytest.approx(measured, rel=0.01)


def test_head_pins_to_stream_end():
    """The head cut's wire cost (T x V logits) dominates any hidden cut —
    the analytic reason optimal schedules never place m_l = N."""
    metas = _stack("attention").cut_meta()
    assert metas[-1].act_bytes > max(m.act_bytes for m in metas[:-1])


def test_zamba_plan_follows_published_hybrid_ids():
    """Zamba2-7B's 81 layers: a hybrid cut point at each of the 13
    published hybrid ids, applying shared blocks 0, 1, 0, ... in turn; the
    stage of layers 18-23 is 5 mamba2 layers and hybrid layer 23
    (application 3, block 1); every cut between the embedding and the
    head carries [h ; e], twice the hidden width."""
    from repro.configs import zamba2_7b
    full = lm_layerstack(zamba2_7b.FULL, seq_len=4096)
    hybrids = [(i - 1, s.block) for i, s in enumerate(full._plan)
               if s.kind == "hybrid"]
    assert [i for i, _ in hybrids] == list(zamba2_7b.FULL.hybrid_layer_ids)
    assert [b for _, b in hybrids] == [j % 2 for j in range(13)]
    assert full.num_layers == 81 + 2
    stage = lm_layerstack(zamba2_7b.FULL.variant(n_layers=6, first_layer=18),
                          seq_len=4096)
    assert [(s.kind, s.block) for s in stage._plan] == [
        ("embed", 0)] + [("mamba2", 0)] * 5 + [("hybrid", 1), ("head", 0)]
    metas = stage.cut_meta()
    assert {m.act_elems for m in metas[:-1]} == {4096 * 2 * 3584}
    assert sum(m.param_count for m in metas) == 1_050_960_352


@pytest.mark.parametrize("family", FAMILIES)
def test_rms_norm_eps_reaches_every_layer(family):
    """``rms_norm_eps`` moves every cut point's output but the
    embedding's; its default leaves the stack as it was."""
    import dataclasses
    cfg = CFGS[family]
    assert cfg.rms_norm_eps == 1e-6
    stack = lm_layerstack(cfg, seq_len=T)
    wide = lm_layerstack(dataclasses.replace(cfg, rms_norm_eps=0.5),
                         seq_len=T)
    params = stack.init(jax.random.PRNGKey(3))
    x, _ = stack.dummy_batch(jax.random.PRNGKey(4), 2)
    for i in range(stack.num_layers):
        a = stack.apply_segment(params, x, i, i + 1)
        b = wide.apply_segment(params, x, i, i + 1)
        assert (i == 0) == bool(jnp.array_equal(a, b)), i
        x = a


def test_mamba2_init_is_mamba2s():
    """A = exp(A_log) in A_init_range; softplus(dt_bias) log-uniform in
    [dt_min, dt_max] and above the floor."""
    from repro.models.lm import ssm
    sc = SSMConfig(d_state=16, head_dim=16)
    p = ssm.init_mamba2(jax.random.PRNGKey(0), 1024, sc, jnp.float32)
    A = np.exp(np.asarray(p["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert A.shape == (128,) and 1.0 <= A.min() and A.max() <= 16.0
    assert A.max() - A.min() > 10
    assert 1e-3 * (1 - 1e-5) <= dt.min() and dt.max() <= 0.1 * (1 + 1e-5)
    assert dt.max() / dt.min() > 20
