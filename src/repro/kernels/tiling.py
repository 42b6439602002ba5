"""Block sizes the TPU compiler accepts.

A Pallas block's last two dims are tiled in (8, 128) units: a block dim
must be the whole array dim or a multiple of the unit.  Picking such a
block here turns a compile-time refusal on the chip into an error at the
call, on any backend.
"""
from __future__ import annotations


def pick_block(n: int, target: int, align: int = 128) -> int:
    """Block length along a dim of length ``n``: all of ``n`` when it
    fits ``target``, else the largest multiple of ``align`` that divides
    ``n`` and fits.  Raise when there is none."""
    if n <= target:
        return n
    b = target - target % align
    while b >= align:
        if n % b == 0:
            return b
        b -= align
    raise ValueError(
        f"no TPU-legal block for a dim of {n}: it is longer than the "
        f"{target}-element block and no multiple of {align} up to "
        f"{target} divides it")
