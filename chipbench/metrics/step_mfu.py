"""Model FLOPs utilization of the whole step, in %: the model FLOPs of
the traced steps (``chipbench/flops.py``) over the traced window's
length, over the chips' bf16 peak."""
from chipbench import trace


def read(rec, tr):
    ws = [w for w in (trace.window(tr, d) for d in sorted(tr.ops)) if w]
    if not ws or not rec.get("steps_traced"):
        return None
    length = sum(w.length_ns for w in ws) / len(ws) / 1e9
    flops = rec["flops_per_step"] * rec["steps_traced"]
    return 100.0 * flops / length / (rec["chips"] *
                                      rec["peaks"]["bf16_flops_per_s"])
