"""Zamba2 in the benchmark, at a tiny size on the CPU: the cell is correct
planned and at a pinned split whose cut carries the ``[h ; e]`` stream,
and comes out not correct when the program drops the gradient into the
embedding stream or leaves out the adapter; each layer kind's FLOPs are
the compiled program's dot FLOPs less the pairs its masks throw away;
the GLA kernel's cost is a brute count; the reference's SSD is the
step-by-step recurrence at published decays; and the configuration file
is the program's preset at the published widths."""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops as F
from chipbench import layers, metrics, run
from chipbench import reference as R
from chipbench.kernels import gla_scan_fwd
from chipbench.layers import zamba2_hybrid, zamba2_mamba
from chipbench.tests import tiny
from repro.configs import zamba2_7b
from repro.models.lm import layerstack, ssm
from repro.models.lm.ssm import SSMConfig

# The program at a tiny size: layers 0-5 with hybrid layers 2 and 5
# (applications 0 and 1, shared blocks 0 and 1).
PROGRAM = zamba2_7b.SMOKE.variant(
    n_layers=6, d_model=32, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=64,
    vocab=128, adapter_rank=4, hybrid_layer_ids=(2, 5),
    ssm=SSMConfig(d_state=8, head_dim=8, chunk=8, n_groups=2))

TINY = {
    "hidden_size": 32, "vocab_size": 128, "intermediate_size": 64,
    "ffn_hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "attention_head_dim": 16,
    "attention_hidden_size": 64, "adapter_rank": 4, "mamba_expand": 2,
    "mamba_d_state": 8, "mamba_headdim": 8, "mamba_ngroups": 2,
    "n_mamba_heads": 8, "mamba_d_conv": 4, "chunk_size": 8,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "dtype": "float32",
    "layers": ["embed", "zamba2_mamba", "zamba2_mamba", "zamba2_hybrid",
               "zamba2_mamba", "zamba2_mamba", "zamba2_hybrid_out",
               "head"],
    "program": {"preset": f"{__name__}:PROGRAM", "overrides": {}},
}
SEED = 2 ** 31 + 7


@pytest.fixture(scope="module", autouse=True)
def keep_compiled_programs():
    """A run frees every compiled program before its reference runs; on
    the CPU that only makes each run compile everything again."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "clear_caches", lambda: None)
        yield


def _run(mix, seed=SEED):
    return run.run(tiny.cell(config=TINY, mix=mix), seed, 0.1, False,
                   require_tpu=False, out=io.StringIO())


@pytest.mark.parametrize("mix", [tiny.PLANNED, tiny.SPLIT],
                         ids=["planned", "split"])
def test_tiny_zamba2_cell_is_correct(mix):
    line = _run(mix)
    assert line["correct"] and line["failed"] == 0, line["checks"]


def test_the_split_cut_carries_the_embedding_stream():
    """The pinned split's cut (3, before hybrid layer 2, which reads
    ``e``) ships ``[h ; e]``: twice the hidden width, in the plan and in
    the activation."""
    cell = tiny.cell(config=TINY, mix=tiny.SPLIT)
    stack = run.program_stack(cell)
    cut, T, D = max(tiny.SPLIT["schedule"]["m_s"]), 32, TINY["hidden_size"]
    assert stack._plan[cut].kind == "hybrid"
    assert stack.cut_meta()[cut - 1].act_elems == T * 2 * D
    params = stack.init(jax.random.PRNGKey(0))
    x, _ = stack.dummy_batch(jax.random.PRNGKey(1), 1)
    assert stack.apply_segment(params, x, 0, cut).shape == (1, T, 2 * D)


def _no_embedding_gradient(e):
    return jnp.concatenate([e, jax.lax.stop_gradient(e)], axis=-1)


@pytest.mark.parametrize("name,fault", [
    ("start_stream", _no_embedding_gradient),
    ("adapt", lambda p, m: jnp.zeros(m.shape[:-1] + (p["up"].shape[1],),
                                     m.dtype)),
], ids=["embedding_gradient_dropped", "adapter_left_out"])
@pytest.mark.parametrize("mix", [tiny.PLANNED, tiny.SPLIT],
                         ids=["planned", "split"])
def test_broken_zamba2_program_is_not_correct(monkeypatch, mix, name,
                                              fault):
    monkeypatch.setattr(layerstack, name, fault)
    line = _run(mix, seed=11)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _masked_pairs(c, kind: str, T: int) -> float:
    """What a kind counts and the compiled program does not: the
    attention scores and SSD pairs its causal masks throw away."""
    out = 0.0
    a = layers.declared(kind, "attention")
    if a is not None:
        a = a(c)
        out += 4.0 * (T * T - F.pairs(T, T, a["window"])) * a["H"] * a["hd"]
    s = layers.declared(kind, "ssd")
    if s is not None:
        s = s(c)
        W = min(s["chunk"], T)
        out += (T // W) * (W * W - W * (W + 1) // 2) \
            * (2 * s["dk"] + 2 * s["dv"]) * s["heads"]
    return -out


def test_zamba2_layer_flops_match_compiled():
    from repro.models.lm.layerstack import hlo_crosscheck_flops
    cell = tiny.cell(config=TINY)
    stack = run.program_stack(cell)
    T = cell.mix["seq_len"]
    for i, kind in enumerate(TINY["layers"]):
        _, hlo = hlo_crosscheck_flops(stack, i)
        assert F.layer_flops(TINY, kind, T) == pytest.approx(
            hlo + _masked_pairs(TINY, kind, T), rel=1e-12), kind


def _gla_call(BH, T, W, dk, dv, act="bf16"):
    """The GLA kernel's instruction text, as the trace names it."""
    fmt = lambda t, s: f"{t}[{','.join(map(str, s))}]{{2,1,0}}"
    ins = [(act, (BH, T, dk)), (act, (BH, T, dk)), (act, (BH, T, dv)),
           ("f32", (BH, T, 128)), ("f32", (BH, 8, T)),
           ("f32", (BH, T // W * 8, max(128, dv)))]
    outs = [(act, (BH, T, dv)), ("f32", (BH, dk, dv)),
            ("f32", (BH, dk, 128))]
    return (f"%gla_scan_fwd = ({', '.join(fmt(*o) for o in outs)}) "
            "custom-call(" + ", ".join(f"{fmt(*i)} %a{j}"
                                       for j, i in enumerate(ins))
            + '), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("T,W,dk,dv", [(64, 16, 8, 8), (96, 32, 4, 12),
                                       (32, 32, 8, 16)])
def test_gla_scan_fwd_cost_is_a_brute_count(T, W, dk, dv):
    BH = 3
    name = _gla_call(BH, T, W, dk, dv)
    assert gla_scan_fwd.match(name)
    flops = 0
    for t in range(T):                   # each position's kept pairs
        for s in range(t - t % W, t + 1):
            flops += 2 * dk + 2 * dv     # q.k and its share of y
        flops += 2 * dk * dv + 2 * dk * dv   # into the state, and from it
    f, b = gla_scan_fwd.cost(name, {})
    assert f == BH * flops
    assert b == BH * (T * (2 * dk * 2 + dv * 2 + dv * 2 + 4) + dk * dv * 4)


def test_gla_scan_fwd_matches_no_flash_call():
    from chipbench.kernels import flash_attention_fwd
    assert not flash_attention_fwd.match(_gla_call(4, 64, 16, 8, 8))


def test_reference_ssd_is_the_recurrence_at_published_decays():
    """The reference's quadratic SSD against the recurrence step by step
    (``gla_decode_step``), with A up to 16 and dt up to 1, so that a
    chunk's log-decay spans hundreds."""
    from repro.models.lm.gla import gla_decode_step
    T, nh, P, G, N = 64, 4, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, nh, P))
    B = jax.random.normal(ks[1], (T, G, N))
    C = jax.random.normal(ks[2], (T, G, N))
    dt = jax.random.uniform(ks[3], (T, nh), minval=0.01, maxval=1.0)
    a = -jax.random.uniform(ks[4], (nh,), minval=1.0, maxval=16.0) * dt
    assert float(-a.sum(0).min()) > 100
    y = zamba2_mamba.scan(TINY, "f32", x, B, C, dt, a)
    rep = lambda g: jnp.repeat(g, nh // G, axis=1)
    S = (jnp.zeros((1, nh, N, P)), jnp.zeros((1, nh, N)))
    want = []
    for t in range(T):
        yt, S = gla_decode_step(rep(C[t:t + 1])[None][:, 0],
                                (rep(B[t:t + 1]) * dt[t, :, None])[None][:, 0],
                                x[t][None], a[t][None], S)
        want.append(yt[0])
    np.testing.assert_allclose(y, jnp.stack(want), rtol=2e-5, atol=2e-5)


def test_published_config_is_the_programs_preset():
    """The cell's configuration file and the program's stage of
    ``zamba2_7b.FULL`` agree on every width and on the weights' layout,
    compared by shape alone (nothing is allocated)."""
    cell = run.load_cell("zamba2-7b-1p.planned")
    c = cell.config
    stack = run.program_stack(cell)
    cfg = stack.cfg
    assert (cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff, cfg.vocab,
            cfg.adapter_rank, cfg.rms_norm_eps, cfg.rope_theta) == (
        c["hidden_size"], c["num_attention_heads"], c["attention_head_dim"],
        c["ffn_hidden_size"], c["vocab_size"], c["adapter_rank"],
        c["rms_norm_eps"], c["rope_theta"])
    assert cfg.n_heads * cfg.hd == c["attention_hidden_size"] \
        == 2 * cfg.d_model
    sc = cfg.ssm
    assert (sc.d_state, sc.head_dim, sc.expand, sc.d_conv, sc.chunk,
            sc.n_groups) == (c["mamba_d_state"], c["mamba_headdim"],
                             c["mamba_expand"], c["mamba_d_conv"],
                             c["chunk_size"], c["mamba_ngroups"])
    assert cfg.hybrid_layer_ids == tuple(c["hybrid_layer_ids"])
    assert (zamba2_mamba.A_RANGE, zamba2_mamba.DT_RANGE,
            zamba2_mamba.DT_FLOOR) == (ssm.A_INIT_RANGE, ssm.DT_RANGE,
                                       ssm.DT_FLOOR) == (
        (1.0, 16.0), (c["time_step_min"], c["time_step_max"]),
        c["time_step_floor"])
    assert [s.kind for s in stack._plan] == [
        "embed"] + ["mamba2"] * 5 + ["hybrid", "head"]
    assert stack._plan[6].block == 1     # application 3 of 2 blocks
    want = jax.eval_shape(stack.init, jax.random.PRNGKey(0))
    specs = R.layer_shapes(c)
    got = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], s[1]), specs,
                       is_leaf=R._is_spec)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(want))
    assert n == 1_050_960_352


def test_zamba2_step_flops_are_pinned():
    """The yardstick of ``step_mfu`` and the kernel rooflines in the
    Zamba2 cell (T 4096, B 2), to the last digit."""
    c = run.load_json(run.ROOT, "chipbench", "configs", "zamba2-7b-1p.json")
    T, B = 4096, 2
    assert F.layer_flops(c, "zamba2_mamba", T) == 664772018176.0
    assert zamba2_hybrid.shared_flops(c, T) == 3010235203584.0
    assert F.layer_flops(c, "zamba2_hybrid_out", T) == 3780233920512.0
    assert F.step_flops(c, T, B) == 48261708644352.0
    bwd = metrics.load("gla_scan_bwd_roofline").step_cost(c, T, B, 2)
    assert bwd[0] == 2 * B * 6 * 112 * gla_scan_fwd.scan_flops(
        T, 256, 64, 64)
    shared = metrics.load("shared_block_roofline").step_flops(c, T, B)
    assert shared == 3 * B * zamba2_hybrid.shared_flops(c, T)
    flash = metrics.load("flash_attention_bwd_roofline").step_cost(
        c, T, B, 2)
    assert flash[0] == 8.0 * 224 * F.pairs(T, T) * 32 * B


@pytest.mark.parametrize("op_name,under", [
    ("jit(hiertrain_step)/jvp(hier.cloud)/layer6.hybrid/shared1/"
     "adapter/dot_general", True),
    ("jit(hiertrain_step)/transpose(jvp(hier.cloud))/layer6.hybrid/"
     "shared0/flash_attention_bwd/flash_attention_bwd_dq", True),
    ("jit(hiertrain_step)/jvp(hier.cloud)/layer6.hybrid/dot_general",
     False),
    ("jit(hiertrain_step)/jvp(hier.cloud)/layer3.mamba2/ssd/"
     "gla_scan_fwd", False),
])
def test_shared_block_scope(op_name, under):
    read = metrics.load("shared_block_roofline")
    assert read.under_shared(op_name) is under
