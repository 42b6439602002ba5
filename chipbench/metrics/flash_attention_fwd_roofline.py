"""Roofline share of the flash-attention forward kernel, in %
(``chipbench/kernels/flash_attention_fwd.py``)."""
from chipbench.kernels import roofline


def read(rec, tr):
    return roofline("flash_attention_fwd", rec, tr)
