"""What names the step program's operations in a trace
(``chipbench/scopes.py``): the compiled program's text read into a
scope map, scope labels, the operations of the step program's runs,
the flash-attention backward's need and reader, and the reader of the
program's compile records."""
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops as F
from chipbench import metrics, run, scopes
from chipbench.trace import Event, Trace

# An excerpt of a compiled step's text, as ``compiled.as_text()`` prints
# it: a fusion with no op_name of its own, a relayout copy of a
# parameter, a negate of it (no relayout, so it inherits nothing from
# its operand), a loop whose body's instructions have none, an escaped
# argument path and a merged op_name.
HLO = r'''HloModule jit_hiertrain_step, entry_computation_layout={...}

%fused_computation.1 (param_0: bf16[8,4]) -> bf16[8,4] {
  %param_0 = bf16[8,4]{1,0} parameter(0)
  ROOT %multiply.3 = bf16[8,4]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(hiertrain_step)/jvp(hier.cloud)/layer1.attn/mul" stack_frame_id=3}
}

%body.2 (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]{1,0}) parameter(0)
  %gte.1 = f32[8,4]{1,0} get-tuple-element(%p), index=1
  ROOT %tuple.4 = (s32[], f32[8,4]{1,0}) tuple(%gte.1, %gte.1)
}

ENTRY %main.9 (params_4___mlp____w_down__.1: bf16[8,4]) -> bf16[8,4] {
  %params_4___mlp____w_down__.1 = bf16[8,4]{1,0} parameter(0), metadata={op_name="params[4][\'mlp\'][\'w_down\']"}
  %copy.7 = bf16[8,4]{0,1:T(8,128)(2,1)} copy(%params_4___mlp____w_down__.1)
  %negate.8 = bf16[8,4]{1,0} negate(%params_4___mlp____w_down__.1)
  %fusion.2 = bf16[8,4]{1,0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1
  %while.21 = (s32[], f32[8,4]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.2, metadata={op_name="jit(hiertrain_step)/transpose(jvp(hier.cloud))/layer3.attn/flash_attention_bwd/while" stack_frame_id=9}
  %fusion.5 = f32[] fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(hiertrain_step)/transpose(jvp(loss))/reduce_sum;jit(hiertrain_step)/jvp(loss)/exp"}
  ROOT %flash_attention_fwd.3 = bf16[8,4]{1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(hiertrain_step)/jvp(hier.stream0)/layer2.attn/flash_attention_fwd"}
}
'''


def test_scope_map_reads_a_compiled_program():
    m = scopes.scope_map(HLO)
    assert m["params_4___mlp____w_down__.1"] == \
        "params[4]['mlp']['w_down']"
    assert m["copy.7"] == "params[4]['mlp']['w_down']"      # its operand's
    assert m["negate.8"] == ""                   # not a relayout: nothing
    assert m["fusion.2"] == \
        "jit(hiertrain_step)/jvp(hier.cloud)/layer1.attn/mul"  # its root's
    assert m["gte.1"] == m["while.21"]                      # its caller's
    assert m["fusion.5"] == "jit(hiertrain_step)/transpose(jvp(loss))/" \
        "reduce_sum"
    assert "main.9" not in m and "fused_computation.1" not in m


def test_scope_map_without_inheritance_keeps_own_names_only():
    own = scopes.scope_map(HLO, inherit=False)
    full = scopes.scope_map(HLO)
    assert own.keys() == full.keys()
    assert own["copy.7"] == own["fusion.2"] == own["gte.1"] == ""
    assert {k: v for k, v in own.items() if v} == \
        {k: full[k] for k, v in own.items() if v}


@pytest.mark.parametrize("op_name,label", [
    ("jit(hiertrain_step)/transpose(jvp(hier.cloud))/layer3.attn/"
     "flash_attention_bwd/while/body/dot_general",
     "hier.cloud/layer3.attn/bwd/flash_attention_bwd"),
    ("jit(hiertrain_step)/jvp(hier.stream0)/layer1.attn/"
     "flash_attention_fwd", "hier.stream0/layer1.attn/fwd/"
     "flash_attention_fwd"),
    ("jit(hiertrain_step)/transpose(jvp(hier.cloud))/layer0.embed/"
     "jit(_take)/scatter-add", "hier.cloud/layer0.embed/bwd"),
    ("jit(hiertrain_step)/hier.update/sub", "hier.update"),
    ("jit(hiertrain_step)/transpose(jvp(loss))/jit(log_softmax)/div",
     "loss/bwd"),
    ("params[4]['mlp']['w_down']", "layer4.params"),
    ("jit(hiertrain_step)/div", "unscoped"),
    ("", "unscoped"),
])
def test_scope_labels(op_name, label):
    assert scopes.scope_of(op_name) == label
    assert bool(scopes.COVERED.search(label)) == (label != "unscoped")


US = 1e3                # ns


BWD = "jit(hiertrain_step)/transpose(jvp(hier.cloud))/layer3.attn/" \
    "flash_attention_bwd/while"
SCOPES = {"while.21": BWD, "fusion.8": BWD + "/body/mul",
          "fusion.9": BWD + "/body/add",
          "fusion.1": "jit(hiertrain_step)/jvp(hier.cloud)/layer0.embed/"
                      "jit(_take)/gather",
          "convolution.4": "jit(hiertrain_step)/jvp(hier.cloud)/"
                           "layer3.attn/dot_general"}


def _synthetic():
    """One step run [0, 10] ms: a flash-backward loop [1, 4] ms with two
    operations inside it, a matmul [5, 7] ms, an operation the scope map
    lacks, and after the run an eager slice program's operation, whose
    name is also the name of an instruction of the step."""
    ops = [Event("%fusion.1 = f32[8] fusion(...)", 0, 500 * US),
           Event("%while.21 = (...) while(...)", 1000 * US, 3000 * US),
           Event("%fusion.8 = f32[8] fusion(...)", 1500 * US, 1000 * US),
           Event("%fusion.9 = f32[8] fusion(...)", 2600 * US, 1000 * US),
           Event("%convolution.4 = bf16[8] convolution(...)", 5000 * US,
                 2000 * US),
           Event("%copy.99 = bf16[8] copy(...)", 8000 * US, 100 * US),
           Event("%fusion.1 = s32[2] fusion(...)", 10500 * US, 10 * US)]
    mods = [Event("jit_hiertrain_step(1)", 0, 10000 * US),
            Event("jit_slice(2)", 10400 * US, 200 * US)]
    return Trace({0: ops}, {0: mods}, [])


BWD_MOD = metrics.load("flash_attention_bwd_roofline")


def test_a_loop_and_the_operations_inside_it_count_once():
    ops = scopes.step_ops(_synthetic(), 0, SCOPES)
    assert scopes.scope_time(ops, BWD_MOD.match) == pytest.approx(3e-3)


def test_step_ops_label_the_step_program_runs_alone():
    ops = scopes.step_ops(_synthetic(), 0, SCOPES)
    got = [(e.name.split(" = ")[0], label) for e, label in ops]
    assert got == [
        ("%fusion.1", "hier.cloud/layer0.embed/fwd"),
        ("%while.21", "hier.cloud/layer3.attn/bwd/flash_attention_bwd"),
        ("%fusion.8", "hier.cloud/layer3.attn/bwd/flash_attention_bwd"),
        ("%fusion.9", "hier.cloud/layer3.attn/bwd/flash_attention_bwd"),
        ("%convolution.4", "hier.cloud/layer3.attn/fwd"),
        ("%copy.99", scopes.UNMAPPED)]          # the eager slice left out
    assert scopes.step_ops(Trace({}, {}, []), 0, SCOPES) == []


def test_flash_backward_need_at_the_phi3_cell():
    cfg = run.load_json(run.ROOT, "chipbench", "configs",
                        "phi3-medium-6l.json")
    f, b = BWD_MOD.step_cost(cfg, 4096, 2, 2)
    assert F.pairs(4096, 4096, 2047) == 6_289_407.5
    assert f == 8 * 128 * 6_289_407.5 * 40 * 6 * 2
    assert f == pytest.approx(3.0913e12, rel=1e-4)
    assert b == 6 * 2 * ((4 * 40 + 4 * 10) * 4096 * 128 * 2 + 40 * 4096 * 4)
    assert b / 819e9 < f / 197e12              # FLOP-bound


CONFIG = {"hidden_size": 8, "vocab_size": 16, "intermediate_size": 8,
          "num_attention_heads": 2, "num_key_value_heads": 1,
          "layers": ["embed", "attn", "head"]}         # hd 4, no window
REC = {"steps_traced": 1, "config": CONFIG,
       "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}}


def test_flash_backward_roofline_reader(monkeypatch):
    tr = _synthetic()
    args = ([{"embed": jax.ShapeDtypeStruct((16, 4), jnp.bfloat16)}], {})
    monkeypatch.setattr(scopes, "step_program",
                        lambda: ("text", args, (1, 8)))
    monkeypatch.setattr(scopes, "scope_map", lambda text: SCOPES)
    f, b = BWD_MOD.step_cost(CONFIG, 8, 1, 2)
    assert (f, b) == (8.0 * 4 * 32 * 2, 12 * 8 * 4 * 2 + 2 * 8 * 4)
    assert BWD_MOD.read(REC, tr) == pytest.approx(
        100 * max(f, b) / 1e6 / 3e-3)
    assert BWD_MOD.read({}, tr) is None
    monkeypatch.setattr(scopes, "scope_map", lambda text: {})
    assert BWD_MOD.read(REC, tr) is None    # nothing under the scope
    monkeypatch.setattr(scopes, "step_program", lambda: None)
    assert BWD_MOD.read(REC, tr) is None    # a program that names nothing


def test_flash_backward_reader_finds_the_programs_own_backward():
    """The tiny cell's step on the Pallas path (interpret mode here):
    its compiled text, read back through ``repro.obs``, puts the scan's
    operations under ``flash_attention_bwd``, and a trace of one of them
    reads as the backward."""
    from chipbench.tests import tiny
    from repro.api import Fleet, plan
    stack = run.program_stack(tiny.cell(mix=dict(tiny.PLANNED,
                                                 backend="pallas")))
    step = plan(stack, Fleet.lm_default(m=2), 2).step_fn(lr=1e-2)
    params = stack.init(jax.random.PRNGKey(0))
    params, _ = step(params, *stack.dummy_batch(jax.random.PRNGKey(1), 2))
    text, args, tokens = scopes.step_program()
    assert tokens == (2, 32)
    m = scopes.scope_map(text)
    bwd = [i for i, op in m.items()
           if "/bwd/flash_attention_bwd" in scopes.scope_of(op)]
    assert bwd
    tr = Trace({0: [Event(f"%{bwd[0]} = f32[8] fusion(...)", 1000 * US,
                          2000 * US)]},
               {0: [Event("jit_hiertrain_step(1)", 0, 5000 * US)]}, [])
    rec = dict(REC, config=tiny.DENSE)
    f, b = BWD_MOD.step_cost(tiny.DENSE, 32, 2, 4)      # float32
    assert BWD_MOD.read(rec, tr) == pytest.approx(
        100 * max(f, b) / 1e6 / 2e-3)


def _records(*events):
    from repro import obs
    c = obs.Compiles()
    for ev in events:
        c.on_duration(*ev[:2], **ev[2])
    return c.done


STEP = "jit(hiertrain_step)"
TRACE_EV, LOWER_EV, COMPILE_EV, LOAD_EV = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec")


def test_step_compile_reader(monkeypatch):
    from repro import obs
    read = metrics.load("setup.step_compile_s").read
    done = _records((TRACE_EV, 1.0, {"fun_name": "hiertrain_step"}),
                    (LOWER_EV, 2.0, {"fun_name": STEP}),
                    (LOAD_EV, 0.5, {}),
                    (COMPILE_EV, 4.0, {"fun_name": STEP}),
                    (LOWER_EV, 8.0, {"fun_name": STEP}),
                    (COMPILE_EV, 16.0, {"fun_name": STEP}))
    monkeypatch.setattr(obs, "snapshot", lambda: done)
    assert read({}, None) == 7.0         # set-up's compile, cache load in
    monkeypatch.setattr(obs, "snapshot", lambda: {})
    assert read({}, None) is None        # no compile of the step
    monkeypatch.setattr(obs, "snapshot", lambda: _records(
        (COMPILE_EV, 4.0, {"fun_name": "jit(hiertrain_reference_step)"})))
    assert read({}, None) is None        # only another program's
    monkeypatch.setattr(scopes, "program_obs", lambda: None)
    assert metrics.load("setup.step_compile_s").read({}, None) is None


def test_program_obs_reads_nothing_from_a_program_without_it(monkeypatch):
    assert scopes.program_obs() is not None
    monkeypatch.setitem(sys.modules, "repro.obs", None)     # not there
    assert scopes.program_obs() is None
    assert scopes.step_program() is None
    assert metrics.load("setup.step_compile_s").read({}, None) is None


def test_program_obs_raises_where_its_import_fails(monkeypatch):
    def broken(name):
        raise ModuleNotFoundError("No module named 'numpy_nowhere'",
                                  name="numpy_nowhere")
    monkeypatch.setattr(scopes.importlib, "import_module", broken)
    with pytest.raises(ModuleNotFoundError):
        scopes.program_obs()
