"""Compile the Pallas kernels of the main path for a TPU v5e that is
described, not attached.

Interpret mode (the CPU suite) cannot see what the chip's compiler
refuses: unaligned blocks, more VMEM than a kernel may use, Mosaic ops
with no lowering.  These tests lower each kernel at the widths the
benchmark runs — Zamba2-7B (arXiv:2411.15242): shared attention 32
heads x 224 with softmax scale (224 / 2) ** -1/2, Mamba2 112 heads x 64
in 2 B/C groups with chunk 256, d_model 3584, T = 4096 — the flash
backward also at Phi-3-medium's widths, and compile it with the TPU
compiler for one chip of a ``v5e:2x2`` topology.  Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports every test file.  ``ops._interpret`` is steered off inside each
test because ``jax.default_backend()`` here is the CPU.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

B, T = 2, 4096
ATTN_HEADS, ATTN_HD = 32, 224
ATTN_SCALE = (ATTN_HD / 2) ** -0.5
SSM_HEADS, SSM_D, SSM_CHUNK = 112, 64, 256
D_MODEL = 3584


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"      # no compiler logs on disk
    try:    # a topology that cannot be described fails every test here
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Kernels off interpret mode, as they run on a TPU backend."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_loss(q, k, v, window=0, scale=None):
    return ops.flash_attention(q, k, v, causal=True, window=window,
                               scale=scale).astype(jnp.float32).sum()


def _assert_named_backward(txt: str) -> None:
    """The backward is the two named Pallas calls under the scope
    ``flash_attention_bwd``, where a profiler trace finds it; no loop
    is left under that scope."""
    for call in ("flash_attention_bwd_dkv", "flash_attention_bwd_dq"):
        assert re.search(rf'%{call}(\.\d+)? = .*custom-call\(.*'
                         r'custom_call_target="tpu_custom_call".*'
                         rf'op_name="[^"]*flash_attention_bwd\)*/{call}/',
                         txt), call
    assert not re.search(r'op_name="[^"]*flash_attention_bwd\)*/while',
                         txt)


def _gla_loss(q, k, v, a):
    y, _ = ops.gla_scan(q, k, v, a, chunk=SSM_CHUNK)
    return y.astype(jnp.float32).sum()


def test_flash_forward_zamba_widths(one_chip, on_chip):
    q = _spec((B, T, ATTN_HEADS, ATTN_HD), jnp.bfloat16, one_chip)
    txt = _compile_text(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            scale=ATTN_SCALE), q, q, q)
    assert "tpu_custom_call" in txt
    # The call keeps its name in the compiled program, where a profiler
    # trace's events find it.
    assert re.search(r"%flash_attention_fwd(\.\d+)? = .*custom-call\(",
                     txt)


def test_flash_backward_zamba_widths(one_chip, on_chip):
    q = _spec((B, T, ATTN_HEADS, ATTN_HD), jnp.bfloat16, one_chip)
    txt = _compile_text(
        jax.value_and_grad(functools.partial(_flash_loss, scale=ATTN_SCALE),
                           argnums=(0, 1, 2)), q, q, q)
    _assert_named_backward(txt)


def test_flash_backward_phi3_widths(one_chip, on_chip):
    """The benchmark's Phi-3-medium attention: 40 query and 10 KV heads
    of 128, T = 4096, B = 2, a 2047-key sliding window."""
    q = _spec((B, T, 40, 128), jnp.bfloat16, one_chip)
    kv = _spec((B, T, 10, 128), jnp.bfloat16, one_chip)
    txt = _compile_text(
        jax.value_and_grad(functools.partial(_flash_loss, window=2047),
                           argnums=(0, 1, 2)), q, kv, kv)
    _assert_named_backward(txt)


@pytest.mark.parametrize("t", [1152, 300])
def test_flash_odd_sequence(one_chip, on_chip, t):
    """T = 1152 takes a 384-row block (a multiple of 128 that is not a
    power of two); T = 300 fits one full-length block."""
    q = _spec((1, t, 8, 128), jnp.bfloat16, one_chip)
    txt = _compile_text(
        jax.value_and_grad(_flash_loss, argnums=(0, 1, 2)), q, q, q)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("normalize", [False, True])
def test_gla_scan_forward_zamba_widths(one_chip, on_chip, normalize):
    q = _spec((B, T, SSM_HEADS, SSM_D), jnp.bfloat16, one_chip)
    a = _spec((B, T, SSM_HEADS), jnp.float32, one_chip)
    txt = _compile_text(
        lambda q, k, v, a: ops.gla_scan(q, k, v, a, chunk=SSM_CHUNK,
                                        normalize=normalize),
        q, q, q, a)
    assert "tpu_custom_call" in txt


def test_gla_scan_backward_zamba_widths(one_chip, on_chip):
    q = _spec((B, T, SSM_HEADS, SSM_D), jnp.bfloat16, one_chip)
    a = _spec((B, T, SSM_HEADS), jnp.float32, one_chip)
    txt = _compile_text(
        jax.value_and_grad(_gla_loss, argnums=(0, 1, 2, 3)), q, q, q, a)
    assert "tpu_custom_call" in txt


def test_mamba2_grouped_zamba_widths(one_chip, on_chip):
    """A whole Mamba2 mixer, forward and backward, with B and C in 2
    groups read by 56 heads each: the GLA kernel takes the heads'
    broadcast q and k."""
    from repro.configs import zamba2_7b
    from repro.models.lm import ssm
    sc = zamba2_7b.FULL.ssm
    assert (ssm.n_ssm_heads(D_MODEL, sc), sc.head_dim, sc.n_groups) == (
        SSM_HEADS, SSM_D, 2)
    p = jax.eval_shape(lambda k: ssm.init_mamba2(k, D_MODEL, sc,
                                                 jnp.bfloat16),
                       jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), p)
    x = _spec((B, T, D_MODEL), jnp.bfloat16, one_chip)

    def loss(p, x):
        return ssm.apply_mamba2(p, x, sc, use_kernel=True).astype(
            jnp.float32).sum()
    txt = _compile_text(jax.value_and_grad(loss, argnums=(0, 1)), p, x)
    assert re.search(r"%gla_scan_fwd(\.\d+)? = .*custom-call\(", txt)


def test_quantize_int8_unaligned_rows(one_chip, on_chip):
    """300 rows: no multiple-of-8 divisor, so the rows are padded."""
    x = _spec((300, 4096), jnp.float32, one_chip)
    txt = _compile_text(
        lambda x: ops.quantize_int8(x, jax.random.PRNGKey(0)), x)
    assert "tpu_custom_call" in txt


def test_wire_qdq_int8_cut_activation(one_chip, on_chip):
    """One row per sample of a T x d_model cut activation (14.7 M
    elements per row): the row must be tiled, not held whole in VMEM."""
    x = _spec((B, T, D_MODEL), jnp.bfloat16, one_chip)
    txt = _compile_text(ops.wire_qdq_int8, x)
    assert "tpu_custom_call" in txt
