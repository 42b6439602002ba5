"""Device time of one hybrid split step over that of one plain
reference step (``jitted_reference_step``) on the same batches and
kernels, both from the traced window on chip 0.  The run traces its
split steps first and the reference steps after them, so the first
step program in the trace is the split step."""
from chipbench import trace


def read(rec, tr):
    if not rec.get("reference_steps_traced"):
        return None
    mods = trace.step_modules(tr, 0)
    if not mods:
        return None
    first = mods[0].name
    split = [m.dur for m in mods if m.name == first]
    ref = [m.dur for m in mods if m.name != first]
    if not split or not ref:
        return None
    return (sum(split) / len(split)) / (sum(ref) / len(ref))
