"""Roofline share of the chunked GLA scan's forward kernel (the SSD of
every Mamba2 layer), in % (``chipbench/kernels/gla_scan_fwd.py``)."""
from chipbench.kernels import roofline


def read(rec, tr):
    return roofline("gla_scan_fwd", rec, tr)
