"""The program's own observability (``repro.obs``): named scopes reach
the compiled step, the step last dispatched can be compiled again to
the same text, the compile records credit what happened to the step,
and ``Plan.step_fn``'s host spans reach a profiler trace.

A tiny dense LM (4 heads of 16, two decoder layers, T = 32, a sliding
window of 12) under HierTrain's sample+layer split: device_0 runs the
embedding and both decoder layers on one sequence, the cloud the other,
merged at cut 3."""
import gc
import glob
import re

import jax
import pytest

from repro import obs
from repro.api import Fleet, plan
from repro.configs.phi3_medium_14b import SMOKE
from repro.core import hybrid_step as hs
from repro.core.cost_model import MultiSchedule
from repro.models.lm.layerstack import lm_layerstack

T, B = 32, 2
SPLIT = MultiSchedule(worker_o="cloud", worker_l="edge",
                      s_workers=("device_0", "device_1"), m_s=(3, 0),
                      m_l=3, b_o=1, b_s=(1, 0), b_l=0)
OP_NAME = re.compile(r'op_name="([^"]*)"')
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")


def _stack(backend):
    cfg = SMOKE.variant(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=96, vocab=128, sliding_window=12,
                        dtype="float32")
    return lm_layerstack(cfg, seq_len=T, backend=backend)


def _split_plan(stack):
    import dataclasses
    p = plan(stack, Fleet.lm_default(m=2), B)
    return dataclasses.replace(p, result=dataclasses.replace(
        p.result, schedule=SPLIT))


def _batch(stack):
    return stack.dummy_batch(jax.random.PRNGKey(1), B)


@pytest.fixture(scope="module", params=["ref", "pallas"])
def compiled_split(request):
    """The split step's compiled text after one step, per backend (the
    Pallas kernels in interpret mode here)."""
    stack = _stack(request.param)
    step = _split_plan(stack).step_fn(lr=1e-2)
    params = stack.init(jax.random.PRNGKey(0))
    params, _ = step(params, *_batch(stack))
    return request.param, _text_of_last_step()


def _text_of_last_step():
    fn, args, _ = obs.last_step()
    return fn.lower(*args).compile().as_text()


def test_compiled_step_names_layers_phases_and_kernels(compiled_split):
    backend, txt = compiled_split
    names = OP_NAME.findall(txt)
    joined = "\n".join(names)
    for scope in ("layer0.embed", "layer1.attn", "layer2.attn",
                  "layer3.head", "loss", "hier.stream0", "hier.cloud",
                  "hier.merge", "hier.exchange", "hier.update"):
        assert scope in joined, scope
    assert all(n.startswith("jit(hiertrain_step)") for n in names
               if n.startswith("jit("))
    # forward under jvp(...), backward under transpose(jvp(...))
    assert re.search(r"jvp\(hier\.stream0\)/layer1\.attn/", joined)
    assert re.search(r"transpose\(jvp\(hier\.cloud\)\)/layer2\.attn/",
                     joined)
    if backend == "pallas":
        for call in ("flash_attention_bwd_dkv", "flash_attention_bwd_dq"):
            assert re.search(r"transpose\(jvp\(hier\.\w+\)\)/layer\d\.attn/"
                             rf"flash_attention_bwd/{call}/", joined), call
        assert re.search(r"jvp\(hier\.\w+\)/layer\d\.attn/"
                         r"flash_attention_fwd", joined)
    else:
        assert "flash_attention" not in joined


def test_reference_step_runs_under_its_scope():
    stack = _stack("ref")
    fn = hs.jitted_reference_step(stack, 1e-2)
    params = stack.init(jax.random.PRNGKey(0))
    x, y = _batch(stack)
    txt = fn.lower(params, x, y).compile().as_text()
    names = [n for n in OP_NAME.findall(txt) if n.startswith("jit(")]
    assert names and all(n.startswith("jit(hiertrain_reference_step)/"
                                      "reference") for n in names)


def test_last_step_is_the_program_that_ran_and_is_held_weakly():
    stack = _stack("ref")
    hs.clear_jit_cache()
    step = _split_plan(stack).step_fn(lr=2e-2)
    params = stack.init(jax.random.PRNGKey(0))
    x, y = _batch(stack)
    fn = hs.jitted_multi_hybrid_step(stack, SPLIT.m_s, SPLIT.m_l, 2e-2)
    direct = fn.lower(params, hs.multi_split_batch(x, y, SPLIT)).compile(
    ).as_text()
    params, _ = step(params, x, y)
    got, args, tokens = obs.last_step()
    assert got is fn and tokens == (B, T)
    assert _text_of_last_step() == direct
    del got, fn, step
    hs.clear_jit_cache()
    gc.collect()
    assert obs.last_step() is None          # the program is not kept


def test_last_step_of_committed_weights_is_served_from_the_cache():
    """Weights placed on a device, as a deployment places them: the
    program that ran is served again from JAX's cache, with no new
    lowering or compile."""
    stack = _stack("ref")
    step = _split_plan(stack).step_fn(lr=4e-2)
    params = jax.device_put(stack.init(jax.random.PRNGKey(0)),
                            jax.devices()[0])
    params, _ = step(params, *_batch(stack))
    before = obs.snapshot()
    _text_of_last_step()
    assert obs.snapshot() == before


def test_first_call_compile_seconds_are_credited_to_the_step():
    stack = _stack("ref")
    hs.clear_jit_cache()
    step = _split_plan(stack).step_fn(lr=3e-2)
    params = stack.init(jax.random.PRNGKey(0))
    x, y = _batch(stack)
    obs.reset()
    params, _ = step(params, x, y)
    first = obs.snapshot()
    rec, = first[obs.STEP_PROGRAM]
    for ev in ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
               "backend_compile_duration"):
        assert rec["/jax/core/compile/" + ev] > 0, ev
    params, _ = step(params, x, y)
    assert obs.snapshot() == first          # no second compile


@pytest.mark.parametrize("name,program", [
    ("hiertrain_step", "hiertrain_step"),
    ("jit(hiertrain_step)", "hiertrain_step"),
    ("jit(hiertrain_reference_step)", None),
    ("jit(_take)", None), ("", None)])
def test_compile_events_are_credited_by_program_name(name, program):
    assert obs.program_of(name) == program


LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def test_cache_load_is_credited_to_the_program_being_compiled():
    c = obs.Compiles()
    c.on_duration(LOWER, 0.5, fun_name="jit(hiertrain_step)")
    c.on_duration(LOAD, 0.25)
    c.on_duration(obs.BACKEND_COMPILE_EVENT, 1.0,
                  fun_name="jit(hiertrain_step)")
    c.on_duration("/jax/some/other_event", 9.0)
    c.on_duration(LOWER, 2.0, fun_name="jit(_take)")
    c.on_duration(LOAD, 3.0)                # another program's load
    c.on_duration(obs.BACKEND_COMPILE_EVENT, 4.0, fun_name="jit(_take)")
    assert c.done == {"hiertrain_step": [
        {LOWER: 0.5, LOAD: 0.25, obs.BACKEND_COMPILE_EVENT: 1.0}]}


def test_two_compiles_of_one_step_give_identical_instruction_names():
    stack = _stack("ref")
    params = stack.init(jax.random.PRNGKey(0))
    x, y = _batch(stack)
    texts = []
    for _ in range(2):
        hs.clear_jit_cache()
        jax.clear_caches()
        fn = hs.jitted_multi_hybrid_step(stack, SPLIT.m_s, SPLIT.m_l, 1e-2)
        texts.append(fn.lower(params, hs.multi_split_batch(x, y, SPLIT))
                     .compile().as_text())
    names = [[m.group(1) for m in map(INSTR.match, t.splitlines()) if m]
             for t in texts]
    assert names[0] and names[0] == names[1]


def test_step_spans_reach_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    stack = _stack("ref")
    params = stack.init(jax.random.PRNGKey(0))
    x, y = _batch(stack)
    jax.profiler.start_trace(str(tmp_path))
    try:
        step = _split_plan(stack).step_fn(lr=1e-2)
        for _ in range(2):
            params, loss = step(params, x, y)
        loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name.split("#")[0] for plane in
             ProfileData.from_file(path).planes for line in plane.lines
             for e in line.events if e.name.startswith("hiertrain.")]
    for span in ("hiertrain.step", "hiertrain.split_batch",
                 "hiertrain.dispatch"):
        assert names.count(span) == 2, span


# Flash-attention backward: the score blocks its grid visits.

def _flash_grad_blocks(B, T, H, KV, causal, window):
    """The block counts recorded while the gradient of one flash
    attention call is traced (shapes only: nothing runs)."""
    import jax.numpy as jnp
    from repro.kernels import ops

    def loss(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=True).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((B, T, H, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, T, KV, 128), jnp.bfloat16)
    before = len(obs.blocks().get("flash_attention_bwd", []))
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    return obs.blocks()["flash_attention_bwd"][before:]


@pytest.mark.parametrize("causal,window,want", [
    (True, 2047, (30, 64)),      # the Phi-3 cells: 2047-key window
    (True, 0, (36, 64)),         # full causal (Zamba2's shared block)
    (False, 0, (64, 64))])
def test_flash_backward_counts_the_blocks_it_visits(causal, window, want):
    got = _flash_grad_blocks(2, 4096, 40, 10, causal, window)
    assert got == [want]


@pytest.mark.parametrize("T,S,bq,bk,causal,window", [
    (4096, 4096, 512, 512, True, 2047), (4096, 4096, 512, 512, True, 0),
    (1024, 1024, 128, 128, False, 0), (1024, 1024, 128, 256, True, 300),
    (1152, 1152, 384, 384, True, 500), (768, 768, 256, 128, True, 2000),
    (768, 512, 128, 128, True, 129), (512, 512, 128, 128, False, 100),
    (300, 300, 300, 300, True, 64)])
def test_flash_band_is_the_blocks_the_mask_keeps(T, S, bq, bk, causal,
                                                  window):
    """``band`` against a brute-force count: a block is kept where the
    mask (the forward's) keeps any of its query-key pairs; the kernels
    skip the iota mask only where it keeps all of them."""
    import numpy as np
    from repro.kernels import flash_attention as fa
    qpos, kpos = np.arange(T)[:, None], np.arange(S)[None, :]
    mask = np.ones((T, S), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= kpos > qpos - window
    blocks = mask.reshape(T // bq, bq, S // bk, bk)
    np.testing.assert_array_equal(fa.band(T, S, bq, bk, causal, window),
                                  blocks.any(axis=(1, 3)))
    i, j = np.arange(T // bq)[:, None], np.arange(S // bk)[None, :]
    _, every = fa.block_pairs(i, j, bq, bk, causal, window)
    np.testing.assert_array_equal(
        np.broadcast_to(every, (T // bq, S // bk)), blocks.all(axis=(1, 3)))
