"""Roofline share of the flash-attention backward, in %: the least time
the chip could take for the traced steps' backward over the device time
of the step program's operations under the scope
``flash_attention_bwd`` (or a call of that name), the union of their
intervals over every chip's traced window.

The program (``kernels/ops.py``) runs the backward under that scope (a
jnp ``lax.scan`` over key blocks today; a Pallas call of that name
later), so its operations are found by the compiled step's scope map
(``chipbench/scopes.py``), not by the shape of one call.  The need is
per step, over every attention layer and sequence: 8*hd FLOPs per
query-key pair the mask keeps (``flops.pairs``) per query head, for
dV, dP, dQ and dK (recomputing the scores counts nothing, as
``flops.py`` counts no recomputation); and the bytes of reading q, k,
v, o and dO and writing dq, dk and dv in the activations' dtype, plus
the forward's f32 log-sum-exp per query.  The larger of its FLOPs over
the bf16 peak and its bytes over the HBM bandwidth, times the steps
traced.  None where the program names no scopes or the trace holds no
such operation."""
import jax

from chipbench import scopes
from chipbench.flops import pairs

NAME = "flash_attention_bwd"


def match(instruction: str, label: str) -> bool:
    """An operation of the backward: under its scope, or its call."""
    return instruction.startswith(NAME) or NAME in label.split("/")


def step_cost(dims, T: int, B: int, act_bytes: int):
    """(FLOPs, bytes) of one step's attention backward, for ``B``
    sequences of ``T`` tokens."""
    n = B * sum(1 for k in dims["layers"] if k == "attn")
    H, KV, hd = dims["H"], dims["KV"], dims["hd"]
    flops = 8.0 * hd * pairs(T, T, dims.get("window", 0)) * H * n
    nbytes = n * ((4 * H + 4 * KV) * T * hd * act_bytes + H * T * 4)
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
    f, b = step_cost(rec["dims"], T, B, act_bytes)
    need = max(f / rec["peaks"]["bf16_flops_per_s"],
               b / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need * rec["steps_traced"] / spent
