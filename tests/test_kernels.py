"""Per-kernel oracle tests: Pallas (interpret=True) vs pure-jnp ref,
swept over shapes and dtypes, plus gradient checks through the custom
VJPs and the model-integration equivalence (use_flash / use_gla_kernel
flags flip nothing numerically)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref
from repro.models.lm import attention as attn
from repro.models.lm.gla import chunked_gla

KEY = jax.random.PRNGKey(0)


def _qkv(B, T, H, KV, hd, S=None, dtype=jnp.float32):
    S = S or T
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,KV,hd", [
    (1, 128, 4, 4, 64),       # MHA
    (2, 256, 4, 2, 64),       # GQA
    (1, 256, 8, 1, 32),       # MQA
    (1, 384, 4, 2, 80),       # non-128 head_dim, odd T blocks
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_matches_ref(B, T, H, KV, hd, causal, window):
    q, k, v = _qkv(B, T, H, KV, hd)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=128, block_k=128, interpret=True)
    want = attn.mha(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_dtypes(dtype, tol):
    q, k, v = _qkv(1, 256, 4, 2, 64, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = attn.mha(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=tol, atol=tol)


def test_flash_lse_matches_ref():
    q, k, v = _qkv(1, 128, 4, 2, 64)
    from repro.kernels.flash_attention import flash_attention_fwd
    qh = q.swapaxes(1, 2).reshape(4, 128, 64)
    kh = k.swapaxes(1, 2).reshape(2, 128, 64)
    vh = v.swapaxes(1, 2).reshape(2, 128, 64)
    o, lse = flash_attention_fwd(qh, kh, vh, causal=True, block_q=64,
                                 block_k=64, interpret=True)
    o_ref, lse_ref = ref.ref_flash_attention(qh, kh, vh, causal=True)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,align,want", [
    (300, 128, 300), (512, 128, 512), (1152, 128, 384), (4096, 128, 512),
    (1000, 128, None), (1200, 8, 400), (600, 8, 200), (1000, 8, 200),
    (1001, 8, None)])
def test_pick_block_is_tpu_legal(n, align, want):
    """A block is the whole dim or a multiple of the (8, 128) tile unit
    that divides it; anything else is refused here, not by the TPU
    compiler.  Target 512 for every case."""
    from repro.kernels.tiling import pick_block
    if want is None:
        with pytest.raises(ValueError, match="no TPU-legal block"):
            pick_block(n, 512, align)
    else:
        assert pick_block(n, 512, align) == want


FLASH_GRAD_CASES = [
    (1, 256, 4, 2, 64, True, 32, 128, jnp.float32, 1e-4, None),
    (1, 256, 4, 2, 64, True, 0, 128, jnp.float32, 1e-4, None),   # causal
    (1, 256, 4, 2, 64, False, 0, 128, jnp.float32, 1e-4, None),  # every
    (1, 256, 4, 2, 64, True, 100, 128, jnp.float32, 1e-4, None),  # w % b
    (1, 256, 4, 2, 64, True, 300, 128, jnp.float32, 1e-4, None),  # w >= T
    (2, 256, 4, 4, 64, True, 160, 128, jnp.float32, 1e-4, None),  # rep 1
    (1, 256, 8, 2, 64, True, 160, 128, jnp.float32, 1e-4, None),  # rep 4
    (2, 300, 4, 2, 64, True, 64, 512, jnp.float32, 1e-4, None),  # 1 block
    (1, 1152, 2, 1, 32, True, 500, 512, jnp.float32, 1e-4, None),  # 384
    (1, 512, 4, 2, 64, True, 200, 128, jnp.bfloat16, 3e-2, None),
    # Zamba2's shared attention: heads of 224, scale (224 / 2) ** -1/2.
    (1, 256, 4, 4, 224, True, 0, 128, jnp.float32, 1e-4, 112 ** -0.5),
]


def _case_id(case) -> str:
    """pytest's id of a case, the default scale left out of it."""
    *head, scale = case
    return "-".join([getattr(v, "__name__", str(v)) for v in head]
                    + ([] if scale is None else [f"scale{scale:.4g}"]))


@pytest.mark.parametrize("B,T,H,KV,hd,causal,window,block,dtype,tol,scale",
                         FLASH_GRAD_CASES,
                         ids=[_case_id(c) for c in FLASH_GRAD_CASES])
def test_flash_grads_match_ref(B, T, H, KV, hd, causal, window, block,
                               dtype, tol, scale):
    """The backward (its Pallas band kernels in interpret mode) against
    autodiff of ``attn.mha`` in f32 on the same inputs.  ``block`` is
    the block target; T = 1152 takes 384-row blocks, T = 300 one;
    ``scale`` None is ``hd ** -0.5``."""
    q, k, v = _qkv(B, T, H, KV, hd, dtype=dtype)

    def f_kernel(q, k, v):
        o = ops.flash_attention(q, k, v, causal=causal, window=window,
                                block_q=block, block_k=block,
                                interpret=True, scale=scale)
        return (o.astype(jnp.float32) ** 2).sum()

    def f_ref(q, k, v):
        return (attn.mha(q, k, v, causal=causal, window=window,
                         scale=scale) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    up = [x.astype(jnp.float32) for x in (q, k, v)]
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(*up)
    for a, b in zip(g1, g2):
        assert a.dtype == dtype
        np.testing.assert_allclose(a.astype(jnp.float32), b, rtol=tol,
                                   atol=tol)


# ---------------------------------------------------------------------------
# GLA scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,dk,dv,chunk", [
    (1, 128, 2, 32, 64, 32),
    (2, 256, 1, 64, 64, 128),
    (1, 64, 4, 16, 48, 64),     # chunk == T
])
@pytest.mark.parametrize("normalize", [False, True])
def test_gla_matches_stepwise_ref(B, T, H, dk, dv, chunk, normalize):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, dv))
    a = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    y, (S, n) = ops.gla_scan(q, k, v, a, chunk=chunk, normalize=normalize,
                             interpret=True)
    qh = q.swapaxes(1, 2).reshape(B * H, T, dk)
    kh = k.swapaxes(1, 2).reshape(B * H, T, dk)
    vh = v.swapaxes(1, 2).reshape(B * H, T, dv)
    ah = a.swapaxes(1, 2).reshape(B * H, T)
    y_ref, S_ref, n_ref = ref.ref_gla(qh, kh, vh, ah, normalize=normalize)
    np.testing.assert_allclose(
        y.swapaxes(1, 2).reshape(B * H, T, dv), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S.reshape(B * H, dk, dv), S_ref,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(n.reshape(B * H, dk), n_ref,
                               rtol=2e-4, atol=2e-4)


def test_gla_kernel_matches_chunked_jnp():
    """Kernel and the model-side chunked jnp path agree."""
    ks = jax.random.split(KEY, 4)
    B, T, H, dk, dv = 2, 128, 2, 32, 32
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, dv))
    a = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    y1, (S1, n1) = chunked_gla(q, k, v, a, chunk=32, use_kernel=False)
    y2, (S2, n2) = ops.gla_scan(q, k, v, a, chunk=32, interpret=True)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S1, S2, rtol=2e-4, atol=2e-4)


def test_gla_grads_match_ref():
    ks = jax.random.split(KEY, 4)
    B, T, H, dk, dv = 1, 64, 2, 16, 16
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, dv))
    a = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))

    def f_kernel(q, k, v, a):
        y, _ = ops.gla_scan(q, k, v, a, chunk=16, interpret=True)
        return (y ** 2).sum()

    def f_ref(q, k, v, a):
        y, _ = chunked_gla(q, k, v, a, chunk=16, use_kernel=False)
        return (y ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(q, k, v, a)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, a)
    for a1, a2 in zip(g1, g2):
        np.testing.assert_allclose(a1, a2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("normalize", [False, True])
def test_gla_grads_finite_at_long_decay_spans(normalize):
    """Log-decays whose sum over a chunk spans 100 and more (Mamba2's A
    up to 16 times dt): the chunked form's gradients are finite and equal
    autodiff of the recurrence run step by step (``gla_decode_step``)."""
    from repro.models.lm.gla import gla_decode_step
    ks = jax.random.split(KEY, 4)
    B, T, H, dk, dv, W = 1, 64, 2, 8, 8, 32
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, dv))
    a = -jax.random.uniform(ks[3], (B, T, H), minval=2.0, maxval=8.0)
    assert float(-a.reshape(B, T // W, W, H).sum(2).max()) >= 100

    def f_chunked(q, k, v, a):
        y, _ = chunked_gla(q, k, v, a, chunk=W, normalize=normalize)
        return (y ** 2).sum()

    def f_steps(q, k, v, a):
        def step(state, xs):
            y, state = gla_decode_step(*xs, state, normalize=normalize)
            return state, y
        state = (jnp.zeros((B, H, dk, dv)), jnp.zeros((B, H, dk)))
        _, ys = jax.lax.scan(step, state, tuple(
            t.swapaxes(0, 1) for t in (q, k, v, a)))
        return (ys ** 2).sum()

    g1 = jax.grad(f_chunked, argnums=(0, 1, 2, 3))(q, k, v, a)
    g2 = jax.grad(f_steps, argnums=(0, 1, 2, 3))(q, k, v, a)
    for x1, x2 in zip(g1, g2):
        assert np.isfinite(np.asarray(x1)).all()
        np.testing.assert_allclose(x1, x2, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# int8 quantization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,N", [(8, 128), (256, 512), (64, 384)])
def test_quant_matches_ref(M, N):
    x = jax.random.normal(KEY, (M, N)) * 3.0
    noise = jax.random.uniform(jax.random.PRNGKey(7), (M, N))
    from repro.kernels.int8_quant import quantize_int8 as kq
    q1, s1 = kq(x, noise, interpret=True)
    q2, s2 = ref.ref_quantize_int8(x, noise)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(s1, s2, rtol=1e-6)


def test_quant_unbiased_and_bounded():
    """Stochastic rounding: unbiased in expectation, error < 1 scale-step."""
    x = jax.random.normal(KEY, (4, 256)) * 2.0
    keys = jax.random.split(jax.random.PRNGKey(3), 64)

    def roundtrip(key):
        q, s = ops.quantize_int8(x, key, interpret=True)
        return ops.dequantize_int8(q, s)

    outs = jax.vmap(roundtrip)(keys)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    err = jnp.abs(outs - x[None])
    assert float(err.max()) <= float(scale.max()) + 1e-6
    bias = jnp.abs(outs.mean(0) - x)
    assert float(bias.max()) < float(scale.max()) * 0.25  # 64-sample mean


def test_model_flash_flag_equivalence():
    """use_flash=True must not change model outputs."""
    from repro.models.lm.model import LMConfig, build_model
    cfg = LMConfig("t", "dense", 2, 64, 4, 2, 128, 64, dtype=jnp.float32)
    toks = jax.random.randint(KEY, (2, 128), 0, 64)
    batch = {"tokens": toks, "targets": toks}
    m1 = build_model(cfg)
    m2 = build_model(cfg.variant(use_flash=True))
    p = m1.init(KEY)
    l1 = m1.loss_fn(p, batch)
    l2 = m2.loss_fn(p, batch)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_model_gla_flag_equivalence():
    from repro.models.lm.model import LMConfig, build_model
    from repro.models.lm.ssm import SSMConfig
    cfg = LMConfig("t", "zamba", 3, 64, 4, 4, 128, 64,
                   ssm=SSMConfig(d_state=16, head_dim=16, chunk=32),
                   shared_attn_every=3, dtype=jnp.float32)
    toks = jax.random.randint(KEY, (2, 64), 0, 64)
    batch = {"tokens": toks, "targets": toks}
    m1 = build_model(cfg)
    m2 = build_model(cfg.variant(use_gla_kernel=True))
    p = m1.init(KEY)
    np.testing.assert_allclose(m1.loss_fn(p, batch), m2.loss_fn(p, batch),
                               rtol=1e-5)
