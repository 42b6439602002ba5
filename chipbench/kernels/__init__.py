"""One module per kernel: how to find its calls in a trace and the
FLOPs and bytes each call needs.

A kernel module defines ``match(event_name) -> bool`` and
``cost(event_name, dims) -> (flops, bytes)``: the operations and the
HBM bytes the algorithm needs for the call's shapes (read from the
instruction's text), not what the kernel happens to move.
"""
from __future__ import annotations

import importlib
from typing import Optional

from chipbench import trace as tr_mod


def load(name: str):
    return importlib.import_module(f"chipbench.kernels.{name}")


def roofline(kernel: str, rec, tr) -> Optional[float]:
    """Share of the roofline, in %: the least time the chip could take
    for the kernel's calls (the larger of FLOPs over the bf16 peak and
    bytes over the HBM bandwidth, per call) over the calls' summed device
    time, over every chip's traced window.  None when no call ran."""
    k = load(kernel)
    peak_f = rec["peaks"]["bf16_flops_per_s"]
    peak_b = rec["peaks"]["hbm_bytes_per_s"]
    need = spent = 0.0
    for dev in sorted(tr.ops):
        w = tr_mod.window(tr, dev)
        if w is None:
            continue
        for e in tr.ops[dev]:
            if w.lo <= e.start and e.end <= w.hi and k.match(e.name):
                f, b = k.cost(e.name, rec["dims"])
                need += max(f / peak_f, b / peak_b)
                spent += e.dur / 1e9
    return None if spent == 0 else 100.0 * need / spent
