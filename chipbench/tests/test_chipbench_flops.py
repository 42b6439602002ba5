"""``flops.py`` and the kernel modules against the FLOPs XLA counts in
the compiled program, at a smoke size on the CPU.

The compiled count is of what the program computes; the benchmark
counts what the algorithm needs.  The difference is exactly the term
its docstrings name: the attention scores that the causal mask, and
the sliding window where there is one, throw away."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops as F
from chipbench import layers, run
from chipbench.kernels import flash_attention_fwd
from chipbench.tests import tiny


def _hlo_flops(fn, *args) -> float:
    from repro.launch.hlo_analysis import loop_aware_cost
    return float(loop_aware_cost(jax.jit(fn).lower(*args).compile()
                                 .as_text())[0])


def _not_in_hlo(config, kind: str, T: int) -> float:
    """The scores a declared attention's mask throws away."""
    declare = layers.declared(kind, "attention")
    if declare is None:
        return 0.0
    a = declare(config)
    return -4.0 * (T * T - F.pairs(T, T, a["window"])) * a["H"] * a["hd"]


def _full(config):
    """The configuration with no sliding window."""
    prog = dict(config["program"], overrides=dict(
        config["program"]["overrides"], sliding_window=0))
    return dict(config, sliding_window=0, program=prog)


@pytest.mark.parametrize("config", [_full(tiny.DENSE), tiny.DENSE],
                         ids=["dense", "dense_window"])
def test_layer_flops_match_compiled(config):
    from repro.models.lm.layerstack import hlo_crosscheck_flops
    cell = tiny.cell(config)
    stack = run.program_stack(cell)
    T = cell.mix["seq_len"]
    for i, kind in enumerate(config["layers"]):
        _, hlo = hlo_crosscheck_flops(stack, i)
        assert F.layer_flops(config, kind, T) == pytest.approx(
            hlo + _not_in_hlo(config, kind, T), rel=1e-12), kind
    assert F.step_flops(config, T, 2) == 6.0 * sum(
        F.layer_flops(config, k, T) for k in config["layers"])


def test_phi3_step_flops_are_pinned():
    """The yardstick of ``step_mfu`` in the Phi-3 cells (T 4096, B 2),
    to the last digit."""
    cfg = run.load_json(run.ROOT, "chipbench", "configs",
                        "phi3-medium-6l.json")
    assert F.layer_flops(cfg, "embed", 4096) == 0.0
    assert F.layer_flops(cfg, "attn", 4096) == 2920535808000.0
    assert F.layer_flops(cfg, "head", 4096) == 1344861634560.0
    assert F.step_flops(cfg, 4096, 2) == 113208458895360.0


def _call(outs, ins):
    """A custom call's instruction text, as the trace names it."""
    fmt = lambda t, s: f"{t}[{','.join(map(str, s))}]{{2,1,0}}"
    return (f"%k = ({', '.join(fmt(*o) for o in outs)}) custom-call("
            + ", ".join(f"{fmt(*i)} %a{j}" for j, i in enumerate(ins))
            + '), custom_call_target="tpu_custom_call"')


BH, T, HD = 4, 128, 32
FLASH = _call([("bf16", (BH, T, HD)), ("f32", (BH, T, 128))],
              [("bf16", (BH, T, HD))] * 3)


def test_flash_cost_is_half_of_full_attention():
    from repro.models.lm.attention import mha
    q = jnp.zeros((1, T, BH, HD), jnp.bfloat16)
    assert flash_attention_fwd.match(FLASH)
    f, _ = flash_attention_fwd.cost(FLASH, {})
    assert 2 * f == _hlo_flops(lambda a: mha(a, a, a, causal=True), q)


@pytest.mark.parametrize("window", [1, 48, T - 1])
def test_flash_cost_under_a_window_counts_the_kept_pairs(window):
    """Under a sliding window the call needs 4*hd FLOPs per query-key
    pair the mask keeps (counted here one query at a time; the
    closed form leaves out half a pair per query)."""
    kept = sum(min(i + 1, window) for i in range(T))
    f, b = flash_attention_fwd.cost(FLASH, {"window": window})
    assert f == pytest.approx(4.0 * BH * HD * kept, rel=1 / T)
    assert f < flash_attention_fwd.cost(FLASH, {})[0]
    assert b == flash_attention_fwd.cost(FLASH, {})[1]


def test_pairs_meets_causal_at_the_full_window():
    assert F.pairs(T, T, T) == F.pairs(T, T, 0) == T * T / 2
    assert F.pairs(T, T, T - 1) < T * T / 2
