"""Roofline share of the chunked GLA scan's backward, in %: the least
time the chip could take for the traced steps' scan backward over the
device time of the step program's operations under the scope
``gla_scan_bwd``, the union of their intervals over every chip's traced
window (``chipbench/scopes.py``).

The program (``kernels/ops.py``) runs the backward of its Pallas scan
as the chunked ``jax.numpy`` recurrence under that scope.  The need is
per step, over every layer whose kind declares the scan it runs
(``ssd(c)`` in ``chipbench/layers/<kind>.py``) and every sequence:
twice the forward's FLOPs (``kernels/gla_scan_fwd.scan_flops``; the
recomputed forward counts nothing), and the bytes of reading q, k, v
and dy and writing dq, dk and dv in the activations' dtype, plus the f32
log-decay read and its gradient written.  The larger of its FLOPs over
the bf16 peak and its bytes over the HBM bandwidth, times the steps
traced.  None where the program names no scopes or the trace holds no
such operation."""
import jax

from chipbench import layers, scopes
from chipbench.kernels.gla_scan_fwd import scan_flops

NAME = "gla_scan_bwd"


def match(instruction: str, label: str) -> bool:
    return NAME in label.split("/")


def step_cost(c, T: int, B: int, act_bytes: int):
    """(FLOPs, bytes) of one step's scan backward, for ``B`` sequences of
    ``T`` tokens of configuration ``c``."""
    flops = nbytes = 0.0
    for kind in dict.fromkeys(c["layers"]):
        declare = layers.declared(kind, "ssd")
        if declare is None:
            continue
        s, n = declare(c), B * c["layers"].count(kind)
        H, dk, dv = s["heads"], s["dk"], s["dv"]
        flops += 2.0 * H * scan_flops(T, min(s["chunk"], T), dk, dv) * n
        nbytes += n * H * T * ((4 * dk + 3 * dv) * act_bytes + 2 * 4)
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
