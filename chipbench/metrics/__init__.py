"""One module per per-layer metric, found by the metric's name in
``BENCHMARK.json`` (``metrics/<name>.py``).

Each defines ``read(rec, tr) -> float | None``: ``rec`` is the run's
record (see ``chipbench/run.py``), ``tr`` the reduced trace of its
traced window (``chipbench/trace.py``).  A reader that finds nothing to
read returns None, and the metric is left out of the run's line.
"""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str):
    """The reader of metric ``name``; names may hold dots."""
    path = os.path.join(HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics._{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
