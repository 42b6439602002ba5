"""Per-architecture smoke tests: reduced same-family config, one forward
/ train step / prefill+decode on CPU; output shapes + finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models.lm.model import build_model

KEY = jax.random.PRNGKey(0)


def _smoke_batch(spec, T, B):
    cfg = spec.smoke
    ks = jax.random.split(KEY, 3)
    toks = jax.random.randint(ks[0], (B, T), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(ks[1], (B, T, cfg.d_model),
                                            jnp.float32)
    elif cfg.n_frontend_tokens > 0:
        P = cfg.n_frontend_tokens
        batch["tokens"] = batch["tokens"][:, :T - P]
        batch["targets"] = batch["targets"][:, :T - P]
        batch["embeds"] = jax.random.normal(ks[2], (B, P, cfg.d_model),
                                            jnp.float32)
    return batch


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_smoke_train_step(arch_id):
    spec = get_arch(arch_id)
    model = build_model(spec.smoke)
    B, T = spec.smoke_batch, spec.smoke_seq
    batch = _smoke_batch(spec, T, B)
    params = model.init(KEY)
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss)), arch_id
    gnorm = jnp.sqrt(sum((g.astype(jnp.float32) ** 2).sum()
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, arch_id


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_smoke_prefill_decode(arch_id):
    spec = get_arch(arch_id)
    cfg = spec.smoke
    model = build_model(cfg)
    B, T = spec.smoke_batch, spec.smoke_seq
    batch = _smoke_batch(spec, T, B)
    params = model.init(KEY)
    max_len = T + 8
    logits, cache = model.prefill(params, batch, max_len)
    assert logits.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch_id
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.int32(T if cfg.family != "dense" or "embeds" not in batch
                    else T)
    logits2, cache = model.decode_step(params, tok, cache, pos)
    assert logits2.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32))), arch_id


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_full_config_matches_assignment(arch_id):
    """The FULL configs carry the exact published dimensions."""
    want = {
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 0, 151936),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
    }[arch_id]
    c = get_arch(arch_id).lm
    got = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab)
    assert got == want, (arch_id, got, want)


def test_moe_expert_counts():
    g = get_arch("grok-1-314b").lm.moe
    assert (g.n_experts, g.top_k) == (8, 2)
    q = get_arch("qwen2-moe-a2.7b").lm.moe
    assert (q.n_experts, q.top_k, q.n_shared) == (60, 4, 4)


def test_long500k_only_for_subquadratic():
    for arch_id, spec in ARCHS.items():
        runs_long = "long_500k" in spec.shapes
        assert runs_long == spec.lm.sub_quadratic, arch_id
        if not runs_long:
            assert "long_500k" in spec.skips
    assert ARCHS["zamba2-7b"].lm.sub_quadratic
    assert ARCHS["xlstm-350m"].lm.sub_quadratic


def test_param_counts_near_published():
    """Total parameter counts are within tolerance of the model names."""
    import jax
    from repro.models.lm.model import param_count
    # eval_shape the FULL init — no allocation.
    checks = {"grok-1-314b": (314e9, 0.12), "pixtral-12b": (12e9, 0.15),
              "phi3-medium-14b": (14e9, 0.15), "gemma3-12b": (12e9, 0.20),
              "qwen2.5-3b": (3e9, 0.25), "granite-20b": (20e9, 0.15),
              "zamba2-7b": (7e9, 0.25),
              # our mLSTM keeps full-width q/k/v and untied embeddings,
              # which lands ~0.52B against the published 350M name.
              "xlstm-350m": (350e6, 0.55)}
    for arch_id, (want, tol) in checks.items():
        cfg = get_arch(arch_id).lm
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
        assert abs(n - want) / want < tol, (arch_id, n, want)


def test_chunked_xent_matches_gather_reference():
    """The chunked loss picks the gold logit by a one-hot select; it must
    match the plain log-softmax gather to f32 rounding, gradient
    included."""
    from repro.models.lm.common import chunked_softmax_xent
    kh, kw, ky = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(kh, (2, 64, 16), jnp.float32)
    w = jax.random.normal(kw, (16, 50), jnp.float32)
    y = jax.random.randint(ky, (2, 64), 0, 50)

    def ref(h, w):
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()

    got, g_got = jax.value_and_grad(
        lambda h, w: chunked_softmax_xent(h, w, y, chunk=16),
        argnums=(0, 1))(h, w)
    want, g_want = jax.value_and_grad(ref, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
