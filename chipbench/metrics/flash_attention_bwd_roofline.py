"""Roofline share of the flash-attention backward, in %: the least time
the chip could take for the traced steps' backward over the device time
of the step program's operations under the scope
``flash_attention_bwd`` (or a call of that name), the union of their
intervals over every chip's traced window.

The program (``kernels/flash_attention.py``) runs the backward as two
Pallas calls, ``flash_attention_bwd_dkv`` and ``flash_attention_bwd_dq``,
under that scope, so its operations are found by the compiled step's
scope map (``chipbench/scopes.py``), not by the shape of one call.  The
need is per step, over every layer that declares a flash-attention call
(``attention(c)`` in ``chipbench/layers/<kind>.py``) and every
sequence, each with its own heads, head size and window: 8*hd FLOPs per
query-key pair the mask keeps (``flops.pairs``) per query head, for dV,
dP, dQ and dK (recomputing the scores counts nothing, as ``flops.py``
counts no recomputation); and the bytes of reading q, k, v, o and dO and
writing dq, dk and dv in the activations' dtype, plus the forward's f32
log-sum-exp per query.  The larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth, times the steps traced.  None where
the program names no scopes or the trace holds no such operation."""
import jax

from chipbench import layers, scopes
from chipbench.flops import pairs

NAME = "flash_attention_bwd"


def match(instruction: str, label: str) -> bool:
    """An operation of the backward: under its scope, or its call."""
    return instruction.startswith(NAME) or NAME in label.split("/")


def step_cost(c, T: int, B: int, act_bytes: int):
    """(FLOPs, bytes) of one step's attention backward, for ``B``
    sequences of ``T`` tokens of configuration ``c``."""
    flops = nbytes = 0.0
    for kind in dict.fromkeys(c["layers"]):
        declare = layers.declared(kind, "attention")
        if declare is None:
            continue
        a, n = declare(c), B * c["layers"].count(kind)
        H, KV, hd = a["H"], a["KV"], a["hd"]
        flops += 8.0 * hd * pairs(T, T, a["window"]) * H * n
        nbytes += n * ((4 * H + 4 * KV) * T * hd * act_bytes + H * T * 4)
    return flops, float(nbytes)


def read(rec, tr):
    if not tr.ops or not rec.get("steps_traced"):
        return None
    prog = scopes.step_program()
    if prog is None:
        return None
    text, args, (B, T) = prog
    m = scopes.scope_map(text)
    spent = sum(scopes.scope_time(scopes.step_ops(tr, d, m), match)
                for d in sorted(tr.ops))
    if spent == 0:
        return None
    # Activations are in the dtype of the embedding table (layer 0's
    # weights), whose rows start the residual stream.
    act_bytes = jax.tree.leaves(args[0][0])[0].dtype.itemsize
    f, b = step_cost(rec["config"], T, B, act_bytes)
    need = max(f / rec["peaks"]["bf16_flops_per_s"],
               b / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need * rec["steps_traced"] / spent
