"""Roofline share of Zamba2's shared transformer blocks, in %: the least
time the chip could take for the traced steps' shared blocks, forward
and backward, over the device time of the step program's operations
under a scope ``shared<k>`` (the program's name for an application of
shared block ``k``, adapter and attention kernels included), the union
of their intervals over every chip's traced window.

The need is per step, over every layer whose kind gives the shared
block's forward model FLOPs (``shared_flops(c, T)`` in
``chipbench/layers/<kind>.py``) and every sequence: three times them
(the backward is two matmuls per forward one), over the bf16 peak,
times the steps traced.  The block is matmuls at these widths, so its
bytes are left out.  None where the program names no such scope or the
trace holds no such operation."""
import re

from chipbench import layers, scopes

SHARED = re.compile(r"(?:^|/)shared\d+(?:/|$)")


def under_shared(op_name: str) -> bool:
    """Whether an ``op_name`` lies under a ``shared<k>`` scope, forward
    (``jvp(...)``) or backward (``transpose(...)``)."""
    return bool(SHARED.search(re.sub(r"(?:transpose|jvp)\(", "", op_name)
                              .replace(")", "")))


def step_flops(c, T: int, B: int) -> float:
    total = 0.0
    for kind in dict.fromkeys(c["layers"]):
        f = layers.declared(kind, "shared_flops")
        if f is not None:
            total += 3.0 * B * c["layers"].count(kind) * f(c, T)
    return total


def read(rec, tr):
    if not tr.ops or not rec.get("steps_traced"):
        return None
    prog = scopes.step_program()
    if prog is None:
        return None
    text, _, (B, T) = prog
    mine = {k: v for k, v in scopes.scope_map(text).items()
            if under_shared(v)}
    spent = sum(scopes.scope_time(scopes.step_ops(tr, d, mine),
                                  lambda _, label: label != scopes.UNMAPPED)
                for d in sorted(tr.ops))
    f = step_flops(rec["config"], T, B)
    if spent == 0 or f == 0:
        return None
    return 100.0 * f / rec["peaks"]["bf16_flops_per_s"] \
        * rec["steps_traced"] / spent
