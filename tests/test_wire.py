"""Wire-compression suite (ISSUE 7): int8 error budget, compression-
aware cost model, asymmetric fwd/bwd byte accounting vs DES transfer
sizes, and the ``wire="none"`` bit-identity guarantee.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import MultiSchedule, Schedule
from repro.core.hybrid_step import (hybrid_sgd_step,
                                    hybrid_step_from_schedule,
                                    jitted_hybrid_step, traffic)
from repro.core.wire import (SCALE_BYTES, apply_wire, int8_wire_bytes,
                             validate_wire, wire_act_bytes, wire_codec,
                             wire_grad_bytes)
from repro.kernels import ops as kops
from repro.models.cnn import DenseSpec, LayeredModel
from tests._compat import given, settings, st

jax.config.update("jax_platform_name", "cpu")

# Pinned error budgets ------------------------------------------------------

# Per-row round-to-nearest bound: |x - qdq(x)| <= absmax/127 / 2.
ROUNDTRIP_SLACK = 1e-5
# 20-step compressed vs uncompressed training on the tiny MLP (measured
# max gap 0.0031; pinned with ~6x margin).
E2E_LOSS_GAP = 0.02


def _tiny_mlp(n_dense: int = 4, width: int = 16) -> LayeredModel:
    specs = tuple(DenseSpec(f"fc{i}", width)
                  for i in range(n_dense - 1)) + \
        (DenseSpec("out", 5, relu=False),)
    return LayeredModel("tiny_mlp", specs, (8,), 5)


def _lm_stack(seq_len: int = 64):
    from repro.models.lm.layerstack import lm_layerstack
    from repro.models.lm.model import LMConfig
    cfg = LMConfig(name="wire-lm", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab=512)
    return lm_layerstack(cfg, seq_len=seq_len)


# ---------------------------------------------------------------------------
# Codec round-trip error bound (property, per tensor distribution)
# ---------------------------------------------------------------------------


def _rows(kind: str, key, b: int, n: int) -> jax.Array:
    k0, k1 = jax.random.split(key)
    if kind == "normal":
        return jax.random.normal(k0, (b, n), jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(k0, (b, n), jnp.float32, -2.0, 2.0)
    if kind == "heavy_tail":
        return jnp.exp(1.5 * jax.random.normal(k0, (b, n), jnp.float32)) \
            * jnp.sign(jax.random.normal(k1, (b, n), jnp.float32))
    if kind == "one_hot_spike":
        base = 1e-3 * jax.random.normal(k0, (b, n), jnp.float32)
        return base.at[:, 0].set(50.0)
    assert kind == "zeros"
    return jnp.zeros((b, n), jnp.float32)


@settings(max_examples=15, deadline=None)
@given(
    b=st.sampled_from([1, 4, 7]),
    n=st.sampled_from([16, 100, 333]),
    kind=st.sampled_from(["normal", "uniform", "heavy_tail",
                          "one_hot_spike", "zeros"]),
    seed=st.integers(min_value=0, max_value=2 ** 16),
)
def test_int8_roundtrip_error_bound(b, n, kind, seed):
    x = _rows(kind, jax.random.PRNGKey(seed), b, n)
    y = kops.wire_qdq_int8(x, interpret=True)
    assert y.shape == x.shape and y.dtype == x.dtype
    absmax = np.max(np.abs(np.asarray(x)), axis=1)
    bound = np.maximum(absmax, 1e-30) / 127.0 / 2.0
    err = np.max(np.abs(np.asarray(y) - np.asarray(x)), axis=1)
    assert np.all(err <= bound * (1.0 + ROUNDTRIP_SLACK) + 1e-12), \
        (kind, float(np.max(err - bound)))


def test_qdq_deterministic_and_jit_pure():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 8), jnp.bfloat16)
    codec = wire_codec("int8")
    f = jax.jit(codec)
    a, b, c = codec(x), f(x), f(x)
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    np.testing.assert_array_equal(np.asarray(b, np.float32),
                                  np.asarray(c, np.float32))


def test_codec_backward_quantizes_cotangent():
    """The custom VJP must push the cotangent through the same codec —
    the MG wire — not pass it through untouched."""
    codec = wire_codec("int8")
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 64), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(2), (3, 64), jnp.float32)
    _, vjp = jax.vjp(codec, x)
    (g,) = vjp(ct)
    np.testing.assert_array_equal(
        np.asarray(g), np.asarray(kops.wire_qdq_int8(ct)))
    assert not np.array_equal(np.asarray(g), np.asarray(ct))


def test_measured_wire_bytes_match_accounting():
    """The cost model's ``elems + 4`` bytes/sample is the *measured*
    payload of the codec: one int8 byte per element + one f32 row scale
    per sample."""
    b, elems = 6, 352
    x = jax.random.normal(jax.random.PRNGKey(0), (b, elems), jnp.float32)
    noise = jnp.full((b, elems), 0.5, jnp.float32)
    q, scale = kops.quantize_int8(x, jax.random.PRNGKey(1), interpret=True)
    assert q.shape == (b, elems) and scale.shape == (b,)
    measured = (q.size * q.dtype.itemsize +
                scale.size * scale.dtype.itemsize) / b
    assert measured == float(int8_wire_bytes(elems))
    assert SCALE_BYTES == scale.dtype.itemsize


# ---------------------------------------------------------------------------
# Compression-aware cost model
# ---------------------------------------------------------------------------


def test_validate_wire():
    assert validate_wire("none") == "none"
    assert validate_wire("int8") == "int8"
    with pytest.raises(ValueError, match="wire"):
        validate_wire("fp8")


def test_apply_wire_asymmetric_lm_columns():
    """LM cuts ship bf16 fwd / f32 bwd; both compress to elems + 4, so
    the fwd ratio is ~1/2 and the bwd ratio ~1/4."""
    from repro.api import Fleet
    stack = _lm_stack()
    fleet = Fleet.lm_default(m=1)
    prof = fleet.profile_for(stack)
    comp = apply_wire(prof, stack, "int8")
    metas = stack.cut_meta()
    for i, m in enumerate(metas):
        assert comp.MO[i] == m.resolved_act_elems + SCALE_BYTES
        assert comp.MG[i] == m.resolved_grad_elems + SCALE_BYTES
        assert prof.MG[i] == 2 * prof.MO[i]          # bf16 fwd, f32 bwd
        assert comp.MO[i] == comp.MG[i]              # same element count
    # ratios at the hidden-state cuts
    assert comp.MO[0] / prof.MO[0] == pytest.approx(0.5, rel=1e-2)
    assert comp.MG[0] / prof.MG[0] == pytest.approx(0.25, rel=1e-2)
    # untouched columns ride along
    np.testing.assert_array_equal(comp.MP, prof.MP)
    assert comp.sample_bytes == prof.sample_bytes


def test_apply_wire_none_is_identity():
    from repro.api import Fleet
    stack = _tiny_mlp()
    prof = Fleet.from_table2(m=1).profile_for(stack)
    assert apply_wire(prof, stack, "none") is prof


def test_apply_wire_pinned_profile_f32_fallback():
    """Profile-only fleets (no model) assume f32 payloads: elems =
    bytes / 4."""
    from repro.core.profiler import analytic_profile
    prof = analytic_profile(_tiny_mlp())
    comp = apply_wire(prof, None, "int8")
    np.testing.assert_allclose(comp.MO, prof.MO / 4.0 + SCALE_BYTES)
    np.testing.assert_allclose(comp.MG, prof.MG / 4.0 + SCALE_BYTES)


def _plan_stack():
    """A planning-scale LM (never executed): big enough that the
    optimal schedule actually offloads, so cut crossings exist."""
    from repro.models.lm.layerstack import lm_layerstack
    from repro.models.lm.model import LMConfig
    cfg = LMConfig(name="wire-plan-lm", family="dense", n_layers=12,
                   d_model=1024, n_heads=16, n_kv_heads=8, d_ff=4096,
                   vocab=32000)
    return lm_layerstack(cfg, seq_len=512)


def test_plan_wire_flows_to_aligned_surfaces():
    """plan(wire=) compresses the *planning* profile, so t_total, the
    DES and t_period all see the same MO/MG — and the Plan records the
    mode for execution."""
    from repro.api import Fleet, plan
    stack = _plan_stack()
    p0 = plan(stack, Fleet.lm_default(m=1), 64)
    p1 = plan(stack, Fleet.lm_default(m=1), 64, wire="int8")
    p2 = plan(stack, Fleet.lm_default(m=1, wire="int8"), 64)   # via Fleet
    assert p0.wire == "none" and p1.wire == "int8" and p2.wire == "int8"
    np.testing.assert_array_equal(p1.profile.MO, p2.profile.MO)
    assert p1.t_total == p2.t_total
    assert np.all(p1.profile.MO <= p0.profile.MO)
    # At this scale the planner offloads, so compressed crossings exist…
    s = p1.schedule
    assert (s.m_l > 0 and s.b_l > 0) or \
        any(m > 0 and b > 0 for m, b in zip(s.m_s, s.b_s))
    # …and the DES runs on the compressed profile: replaying the int8
    # plan's schedule against the *uncompressed* profile must be
    # strictly slower (more bytes on the wire, same compute).
    from repro.core import simulator
    sim = p1.simulate()
    assert sim == simulator._simulate_iteration_multi(
        p1.profile, p1.network, p1.schedule)
    assert sim < simulator._simulate_iteration_multi(
        p0.profile, p0.network, p1.schedule)
    assert "wire=int8" in p1.explain()


# ---------------------------------------------------------------------------
# Byte accounting vs DES transfer sizes (the asymmetric MO/MG bugfix)
# ---------------------------------------------------------------------------


def _act_wire_from_profile(prof, sched) -> float:
    """The DES/LP activation-channel bytes: fwd MO + bwd MG at each
    crossing, from the profile columns."""
    act = 0.0
    if sched.m_s > 0 and sched.b_s > 0 and \
            sched.worker_s != sched.worker_o:
        act += sched.b_s * (prof.MO[sched.m_s - 1] +
                            prof.MG[sched.m_s - 1])
    if sched.m_l > 0 and sched.b_l > 0 and \
            sched.worker_l != sched.worker_o:
        act += sched.b_l * (prof.MO[sched.m_l - 1] +
                            prof.MG[sched.m_l - 1])
    return act


@pytest.mark.parametrize("wire", ["none", "int8"])
def test_traffic_matches_des_transfer_sizes(wire):
    """traffic() must pin the byte accounting to the same MO/MG the DES
    and LPs charge — per direction, honoring the LM's bf16-fwd/f32-bwd
    asymmetry (the historical path assumed symmetric dtypes)."""
    from repro.api import Fleet
    stack = _lm_stack()
    fleet = Fleet.lm_default(m=1)
    prof = apply_wire(fleet.profile_for(stack), stack, wire)
    sched = Schedule(worker_o="cloud", worker_s="device_0",
                     worker_l="edge", m_s=1, m_l=2, b_o=4, b_s=5, b_l=7)
    rep = traffic(stack, sched, stack.default_sample_bytes(),
                  origin="device_0", wire=wire)
    assert rep.activation_bytes == pytest.approx(
        _act_wire_from_profile(prof, sched))
    if wire == "int8":
        # compressed is strictly smaller, and *not* what the symmetric
        # assumption (2x act_bytes) would predict
        m = stack.cut_meta()[sched.m_s - 1]
        symmetric = 2 * wire_act_bytes(m, "int8")
        assert wire_act_bytes(m, "int8") + wire_grad_bytes(m, "int8") == \
            pytest.approx(symmetric)  # int8: both directions equal elems+4
        uncompressed = m.act_bytes + m.resolved_grad_bytes
        assert rep.activation_bytes < sched.b_s * uncompressed + \
            sched.b_l * uncompressed


def test_traffic_asymmetric_uncompressed_accounting():
    """wire='none' on an asymmetric stack: fwd bytes come from
    act_bytes (bf16), bwd from grad_bytes (f32) — never a shared
    width."""
    stack = _lm_stack()
    m = stack.cut_meta()[0]
    assert m.resolved_grad_bytes == 2 * m.act_bytes
    sched = Schedule(worker_o="cloud", worker_s="device_0",
                     worker_l="edge", m_s=1, m_l=1, b_o=0, b_s=3, b_l=0)
    rep = traffic(stack, sched, stack.default_sample_bytes(),
                  origin="device_0")
    assert rep.activation_bytes == pytest.approx(
        3 * (m.act_bytes + 2 * m.act_bytes))


# ---------------------------------------------------------------------------
# Execution: bit-identity at wire="none", bounded drift at "int8"
# ---------------------------------------------------------------------------


def _cnn_fixture():
    model = _tiny_mlp()
    sched = MultiSchedule.from_schedule(Schedule(
        worker_o="edge", worker_s="device", worker_l="cloud",
        m_s=2, m_l=3, b_o=8, b_s=8, b_l=8))
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (24, 8), jnp.float32)
    y = jax.random.randint(jax.random.fold_in(key, 2), (24,), 0, 5)
    return model, sched, params, x, y


def test_wire_none_bit_identical_to_seed():
    """The default wire is the identity: same traced program, bitwise
    equal results to the historical (pre-wire) call."""
    model, sched, params, x, y = _cnn_fixture()
    p_legacy, l_legacy = hybrid_step_from_schedule(model, params, x, y,
                                                   sched, 0.05)
    p_none, l_none = hybrid_step_from_schedule(model, params, x, y, sched,
                                               0.05, wire="none")
    assert float(l_legacy) == float(l_none)
    for a, b in zip(jax.tree.leaves(p_legacy), jax.tree.leaves(p_none)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wire_int8_changes_results_within_budget():
    model, sched, params, x, y = _cnn_fixture()
    losses = {}
    for wire in ("none", "int8"):
        p = params
        for step in range(20):
            kx, ky = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(0), step + 1))
            xs = jax.random.normal(kx, (24, 8), jnp.float32)
            ys = jax.random.randint(ky, (24,), 0, 5)
            p, loss = hybrid_step_from_schedule(model, p, xs, ys, sched,
                                                0.05, wire=wire)
            losses.setdefault(wire, []).append(float(loss))
    gaps = [abs(a - b) for a, b in zip(losses["none"], losses["int8"])]
    assert 0.0 < max(gaps) <= E2E_LOSS_GAP, max(gaps)
    # both runs actually train
    assert losses["int8"][-1] < losses["int8"][0]


def test_multi_matches_triple_at_m1_with_wire():
    """The M=1 bit-identity of the triple and star fleets survives the
    codec: both fleets, int8 on the wire and pinned to one split
    schedule, give bit-identical params and loss through
    ``Plan.step_fn``."""
    import dataclasses

    from repro.api import Fleet, plan
    model, msched, params, x, y = _cnn_fixture()
    outs = []
    for topology, s in (("triple", msched.to_schedule()), ("star", msched)):
        p = plan(model, Fleet.from_table2(topology=topology, wire="int8"),
                 24)
        assert p.fleet.topology == topology and p.wire == "int8"
        p = dataclasses.replace(
            p, result=dataclasses.replace(p.result, schedule=s))
        outs.append(p.step_fn(lr=0.05)(jax.tree.map(jnp.array, params),
                                       x, y))
    (p3, l3), (pm, lm) = outs
    assert float(l3) == float(lm)
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(pm)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_jit_cache_keys_include_wire():
    model = _tiny_mlp()
    f_none = jitted_hybrid_step(model, (2,), 3, 0.05)
    f_none2 = jitted_hybrid_step(model, (2,), 3, 0.05, wire="none")
    f_int8 = jitted_hybrid_step(model, (2,), 3, 0.05, wire="int8")
    assert f_none is f_none2
    assert f_int8 is not f_none


def test_codec_skips_input_uploads():
    """A cut at 0 ships raw samples (ints for LMs) — the codec must not
    touch that channel."""
    stack = _lm_stack(seq_len=16)
    params = stack.init(jax.random.PRNGKey(0))
    x, y = stack.dummy_batch(jax.random.PRNGKey(1), 6)
    batches = {"o": (x[:2], y[:2]), "s": ((x[2:4], y[2:4]),),
               "l": (x[4:], y[4:])}
    # m_s = 0: worker_s's samples are raw-input uploads
    p, loss = hybrid_sgd_step(stack, params, batches, (0,), 2, 0.05,
                              wire="int8")
    assert np.isfinite(float(loss))


def test_plan_execution_carries_wire():
    """Plan.step_fn under an int8 fleet runs the codec: results differ
    from the uncompressed plan's step on the same inputs."""
    from repro.api import Fleet, plan
    stack = _lm_stack(seq_len=16)
    B = 12
    p_none = plan(stack, Fleet.lm_default(m=1), B)
    p_int8 = plan(stack, Fleet.lm_default(m=1, wire="int8"), B)
    # same schedule shape requirements; execution must differ only if a
    # compressed crossing actually carries samples
    s0, s1 = p_none.schedule, p_int8.schedule
    x, y = stack.dummy_batch(jax.random.PRNGKey(1), B)
    # step_fn donates params — give each call its own buffers
    out0 = p_none.step_fn(lr=0.05)(stack.init(jax.random.PRNGKey(0)), x, y)
    out1 = p_int8.step_fn(lr=0.05)(stack.init(jax.random.PRNGKey(0)), x, y)
    assert np.isfinite(float(out0[1])) and np.isfinite(float(out1[1]))
    crossing = any(m > 0 and b > 0 for m, b in zip(s0.m_s, s0.b_s)) or \
        (s0.m_l > 0 and s0.b_l > 0)
    if s0 == s1 and crossing:
        assert float(out0[1]) != float(out1[1])
