"""Differential exactness oracles for every Pallas kernel (ISSUE 7).

Each kernel is compared property-style against its pure-jnp oracle in
:mod:`repro.kernels.ref` across dtypes, shapes and causal/window
configs, in ``interpret=True`` mode so the suite runs on the CPU CI
runner (interpret mode executes the kernel body as traced JAX ops —
the same arithmetic the TPU lowering implements).

Tolerances are pinned per (kernel, dtype) as ``atol + ulps * ulp(ref)``:
an absolute floor for cancellation near zero plus a ULP allowance in
the *storage* dtype for the reassociated reductions (online softmax,
chunked scan).  The int8 quantizer is integer-exact — no tolerance.

The suite ends with the end-to-end contract: hybrid-step loss/params
under ``backend="pallas"`` match ``backend="ref"`` within a pinned
bound at several cuts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import gla_scan as gs
from repro.kernels import int8_quant as iq
from repro.kernels import ref
from tests._compat import given, settings, st

jax.config.update("jax_platform_name", "cpu")

# ---------------------------------------------------------------------------
# Pinned tolerances: atol + ulps * ulp_{dtype}(|reference|), per kernel
# per storage dtype.  bf16 has an 8-bit mantissa, so one bf16 ulp is
# 2**16 f32 ulps — the ULP term, not a loose atol, is what absorbs the
# coarser storage grid at large magnitudes.
# ---------------------------------------------------------------------------

TOL = {
    ("flash_o", "float32"): (2e-6, 16.0),
    ("flash_o", "bfloat16"): (1e-3, 4.0),
    ("flash_lse", "float32"): (2e-6, 16.0),   # lse is always f32
    ("flash_lse", "bfloat16"): (2e-5, 64.0),  # bf16 inputs, f32 lse
    ("gla_y", "float32"): (1e-4, 64.0),
    ("gla_y", "bfloat16"): (2e-2, 8.0),
    ("gla_state", "float32"): (1e-4, 64.0),   # S/n carries are f32
    ("gla_state", "bfloat16"): (1e-2, 64.0),
}


def _ulp(want: np.ndarray, dtype) -> np.ndarray:
    """ULP of each reference value in the given storage dtype."""
    w = np.abs(np.asarray(want, np.float32))
    u = np.spacing(np.maximum(w, np.finfo(np.float32).tiny))
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        u = u * 2.0 ** 16          # 24-bit vs 8-bit mantissa
    return u


def assert_oracle_close(kind: str, got, want, dtype) -> None:
    atol, ulps = TOL[(kind, jnp.dtype(dtype).name)]
    g = np.asarray(jax.device_get(got), np.float32)
    w = np.asarray(jax.device_get(want), np.float32)
    assert g.shape == w.shape, (kind, g.shape, w.shape)
    err = np.abs(g - w)
    allowed = atol + ulps * _ulp(w, dtype)
    worst = np.max(err - allowed)
    assert np.all(err <= allowed), (
        f"{kind}[{jnp.dtype(dtype).name}]: max excess {worst:.3e}, "
        f"max err {err.max():.3e} vs atol={atol} + {ulps} ulp")


# ---------------------------------------------------------------------------
# Flash attention vs ref_flash_attention
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    t=st.sampled_from([64, 128]),
    hd=st.sampled_from([32, 64]),
    bkv=st.sampled_from([1, 2]),
    rep=st.sampled_from([1, 2]),       # GQA: BH = BKV * rep
    causal=st.sampled_from([True, False]),
    window=st.sampled_from([0, 32]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    seed=st.integers(min_value=0, max_value=2 ** 16),
)
def test_flash_attention_oracle(t, hd, bkv, rep, causal, window, dtype,
                                seed):
    dt = jnp.dtype(dtype)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k0, (bkv * rep, t, hd), dt)
    k = jax.random.normal(k1, (bkv, t, hd), dt)
    v = jax.random.normal(k2, (bkv, t, hd), dt)
    o, lse = fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                    block_q=min(t, 64), block_k=min(t, 64),
                                    interpret=True)
    o_ref, lse_ref = ref.ref_flash_attention(q, k, v, causal=causal,
                                             window=window)
    assert o.dtype == q.dtype
    assert_oracle_close("flash_o", o, o_ref, dt)
    assert_oracle_close("flash_lse", lse, lse_ref, dt)


# ---------------------------------------------------------------------------
# GLA scan vs ref_gla (the step-recurrence definition)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    bh=st.sampled_from([2, 4]),
    t=st.sampled_from([64, 128]),
    dk=st.sampled_from([16, 32]),
    dv=st.sampled_from([16, 32]),
    chunk=st.sampled_from([32, 64]),
    normalize=st.sampled_from([False, True]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    seed=st.integers(min_value=0, max_value=2 ** 16),
)
def test_gla_scan_oracle(bh, t, dk, dv, chunk, normalize, dtype, seed):
    dt = jnp.dtype(dtype)
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k0, (bh, t, dk), dt)
    k = jax.random.normal(k1, (bh, t, dk), dt) * 0.3
    v = jax.random.normal(k2, (bh, t, dv), dt)
    # log-decay in [-0.25, 0): forgetful enough that the state stays
    # bounded, slow enough that cross-chunk carries matter.
    a = -0.25 * jax.random.uniform(k3, (bh, t), jnp.float32) - 1e-3
    y, S, n = gs.gla_scan_fwd(q, k, v, a, chunk=chunk,
                              normalize=normalize, interpret=True)
    y_ref, S_ref, n_ref = ref.ref_gla(q, k, v, a, normalize=normalize)
    assert y.dtype == v.dtype
    assert_oracle_close("gla_y", y, y_ref, dt)
    assert_oracle_close("gla_state", S, S_ref, dt)
    assert_oracle_close("gla_state", n, n_ref, dt)


# GLA scan backward vs jax.vjp of the chunked jnp reference: the Pallas
# backward (normalize=False, no initial state) through ``ops.gla_scan``'s
# custom VJP, in model layout, for the gradients of q, k, v and the
# log-decay.  Cases: one chunk and several; log-decays whose chunk sum
# spans 100 and more; nonzero cotangents on the returned S and n; q and
# k broadcast over heads from 2 groups, as Mamba2 makes them.

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t,chunk,span,state_cts,groups", [
    (128, 32, False, False, 0),      # several chunks
    (64, 64, False, False, 0),       # one chunk: T == W
    (64, 32, True, False, 0),        # long decays
    (96, 32, False, True, 0),        # cotangents on S and n
    (64, 32, False, False, 2),       # q, k from 2 groups
], ids=["chunks", "one-chunk", "long-decay", "state-cts", "groups"])
def test_gla_scan_bwd_oracle(t, chunk, span, state_cts, groups, dtype):
    from repro.kernels import ops
    from repro.models.lm.gla import chunked_gla
    dt = jnp.dtype(dtype)
    B, H, dk, dv = 2, 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(t + chunk + groups), 7)
    G = groups or H
    qg = jax.random.normal(ks[0], (B, t, G, dk))
    kg = jax.random.normal(ks[1], (B, t, G, dk)) * 0.3
    v = jax.random.normal(ks[2], (B, t, H, dv), dt)
    if span:
        a = -jax.random.uniform(ks[3], (B, t, H), minval=2.0, maxval=8.0)
        assert float(-a.reshape(B, t // chunk, chunk, H).sum(2).max()) >= 100
    else:
        a = -0.25 * jax.random.uniform(ks[3], (B, t, H)) - 1e-3
    cts = (jax.random.normal(ks[4], (B, t, H, dv), dt),
           (jax.random.normal(ks[5], (B, H, dk, dv)) * state_cts,
            jax.random.normal(ks[6], (B, H, dk)) * state_cts))

    def heads(x):       # group g serves heads [g H/G, (g+1) H/G)
        return jnp.repeat(x, H // G, axis=2).astype(dt)

    def scan(gla):
        return lambda qg, kg, v, a: gla(heads(qg), heads(kg), v, a)

    def grads(gla):
        return jax.jit(lambda *xs: jax.vjp(scan(gla), *xs)[1](cts))(
            qg, kg, v, a)

    got = grads(functools.partial(ops.gla_scan, chunk=chunk, interpret=True))
    want = grads(functools.partial(chunked_gla, chunk=chunk))
    assert got[2].dtype == dt
    for kind, g, w in zip(("gla_y", "gla_y", "gla_y", "gla_state"), got,
                          want):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        assert_oracle_close(kind, g, w, dt)


# ---------------------------------------------------------------------------
# Int8 quantizer vs ref_quantize_int8 — integer-exact
# ---------------------------------------------------------------------------


def _draw_rows(kind: str, key, m: int, n: int) -> jax.Array:
    k0, k1 = jax.random.split(key)
    if kind == "normal":
        return jax.random.normal(k0, (m, n), jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(k0, (m, n), jnp.float32, -3.0, 3.0)
    if kind == "heavy_tail":
        return jnp.exp(2.0 * jax.random.normal(k0, (m, n), jnp.float32)) * \
            jnp.sign(jax.random.normal(k1, (m, n), jnp.float32))
    if kind == "constant":
        return jnp.full((m, n), 0.73, jnp.float32)
    assert kind == "zeros"
    return jnp.zeros((m, n), jnp.float32)


@settings(max_examples=12, deadline=None)
@given(
    m=st.sampled_from([1, 3, 8]),
    n=st.sampled_from([8, 127, 256]),
    kind=st.sampled_from(["normal", "uniform", "heavy_tail", "constant",
                          "zeros"]),
    stochastic=st.sampled_from([True, False]),
    seed=st.integers(min_value=0, max_value=2 ** 16),
)
def test_quantize_int8_oracle(m, n, kind, stochastic, seed):
    key = jax.random.PRNGKey(seed)
    x = _draw_rows(kind, key, m, n)
    noise = jax.random.uniform(jax.random.fold_in(key, 1), (m, n),
                               jnp.float32) if stochastic \
        else jnp.full((m, n), 0.5, jnp.float32)
    q, scale = iq.quantize_int8(x, noise, interpret=True)
    q_ref, scale_ref = ref.ref_quantize_int8(x, noise)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    # Quantized codes are integer-exact; the f32 row scale may differ by
    # interpret-mode reduction ordering — pinned at 2 ulps.
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(scale_ref),
                               rtol=2.4e-7, atol=0.0)


def test_quantize_int8_block_tiling_invariance(monkeypatch):
    """Row blocks, lane tiles and padding must not change results: the
    scale is the absmax of the whole row, however it is tiled."""
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 640), jnp.float32)
    noise = jnp.full((20, 640), 0.5, jnp.float32)
    base = iq.quantize_int8(x, noise, block_rows=20, interpret=True)
    # (row block target, tile elements): 8-row blocks over rows padded
    # to 24, then 128-lane tiles (5 per row) with and without row blocks.
    for br, tile in ((8, iq.TILE_ELEMS), (8, 8 * 128), (20, 20 * 256)):
        monkeypatch.setattr(iq, "TILE_ELEMS", tile)
        q, s = iq.quantize_int8(x, noise, block_rows=br, interpret=True)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(base[0]))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(base[1]))


# ---------------------------------------------------------------------------
# End to end: hybrid-step loss/params with backend="pallas" vs "ref".
# A zamba stack exercises *both* kernels (mamba2 -> GLA scan, shared
# attention -> flash) inside the distributed step at several cuts.
# ---------------------------------------------------------------------------

# Pinned e2e bound (f32 compute): kernel-vs-ref differences pass through
# one backward pass and one SGD update.
E2E_PARAM_ATOL = 5e-5
E2E_PARAM_RTOL = 5e-4
E2E_LOSS_RTOL = 1e-5


def _zamba_stacks():
    from repro.models.lm.layerstack import lm_layerstack
    from repro.models.lm.model import LMConfig
    from repro.models.lm.ssm import SSMConfig
    cfg = LMConfig(name="oracle-zamba", family="zamba", n_layers=4,
                   d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                   vocab=512,
                   ssm=SSMConfig(d_state=16, head_dim=16, expand=2,
                                 chunk=32),
                   shared_attn_every=2, dtype=jnp.float32)
    from repro.models.lm.layerstack import LMLayerStack
    assert isinstance(lm_layerstack(cfg, 32, "pallas"), LMLayerStack)
    return (lm_layerstack(cfg, seq_len=32, backend="ref"),
            lm_layerstack(cfg, seq_len=32, backend="pallas"))


@pytest.mark.parametrize("m_s,m_l", [(1, 2), (2, 4), (3, 5)])
def test_hybrid_step_pallas_matches_ref(m_s, m_l):
    from repro.core.hybrid_step import hybrid_sgd_step
    st_ref, st_pal = _zamba_stacks()
    assert st_pal.cfg.use_flash and st_pal.cfg.use_gla_kernel
    # N = embed + (mamba2, hybrid) x 2 + head = 6 cut-points
    params = st_ref.init(jax.random.PRNGKey(0))
    x, y = st_ref.dummy_batch(jax.random.PRNGKey(1), 9)
    batches = {"o": (x[:3], y[:3]), "s": (x[3:6], y[3:6]),
               "l": (x[6:], y[6:])}
    p_ref, loss_ref = hybrid_sgd_step(st_ref, params, batches, m_s, m_l,
                                      lr=0.05)
    p_pal, loss_pal = hybrid_sgd_step(st_pal, params, batches, m_s, m_l,
                                      lr=0.05)
    np.testing.assert_allclose(float(loss_pal), float(loss_ref),
                               rtol=E2E_LOSS_RTOL)
    flat_r = jax.tree.leaves(p_ref)
    flat_p = jax.tree.leaves(p_pal)
    assert len(flat_r) == len(flat_p)
    for a, b in zip(flat_r, flat_p):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   atol=E2E_PARAM_ATOL,
                                   rtol=E2E_PARAM_RTOL)


def test_backend_profiles_identical():
    """The kernel switch must not perturb planning: cut meta (and hence
    profiles and schedules) is backend-independent."""
    st_ref, st_pal = _zamba_stacks()
    for a, b in zip(st_ref.cut_meta(), st_pal.cut_meta()):
        assert a == b
    assert st_ref.name == st_pal.name


def test_backend_validation():
    from repro.models.lm.layerstack import lm_layerstack
    from repro.models.lm.model import LMConfig
    cfg = LMConfig(name="t", family="dense", n_layers=1, d_model=32,
                   n_heads=2, n_kv_heads=2, d_ff=64, vocab=128)
    with pytest.raises(ValueError, match="backend"):
        lm_layerstack(cfg, seq_len=16, backend="tpu")
