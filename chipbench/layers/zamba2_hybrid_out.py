"""Zamba2's hybrid layer (``zamba2_hybrid``) as the last layer before
the head: its output is ``h'`` alone ([T, D]), the embedding stream
ending here."""
from __future__ import annotations

from typing import Dict

from chipbench.layers import zamba2_hybrid as hybrid

INIT = hybrid.INIT
attention, flops, shapes, shared_flops, ssd = (
    hybrid.attention, hybrid.flops, hybrid.shapes, hybrid.shared_flops,
    hybrid.ssd)


def forward(c: Dict, mode: str, p, h):
    return hybrid.forward(c, mode, p, h)[:, :c["hidden_size"]]
