"""Share of the traced window in which no operation ran on the device,
in %, averaged over the chips: 1 - (union of the XLA op intervals) /
(first step program's start to the last one's end)."""
from chipbench import trace


def read(rec, tr):
    ws = [w for w in (trace.window(tr, d) for d in sorted(tr.ops)) if w]
    if not ws:
        return None
    return 100.0 * sum(1.0 - w.busy_ns / w.length_ns for w in ws) / len(ws)
