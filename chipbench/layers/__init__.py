"""One module per decoder-layer kind: its weights, its plain reference and
its model FLOPs, found by the kind's name in a configuration's
``layers`` (``layers/<kind>.py``).

The chain's two ends, ``embed`` (token ids in) and ``head`` (loss out),
stay in ``chipbench/reference.py`` and ``chipbench/flops.py``, which
treat them apart.  Every other kind is a module here, or in another
directory on this package's ``__path__``, that defines:

* ``shapes(c) -> dict``: the leaves of one cut point's weights as
  ``(shape, dtype, init)``, in the program's parameter layout; ``init``
  is ``"matrix"`` (truncated normal, std 1/sqrt(fan_in)), ``"zeros"``
  or a name in ``INIT``.
* ``forward(c, mode, p, h) -> h``: the layer on one sequence, ``h``
  ``[T, D]`` float32 and ``p`` its weights in float32.  Every matmul goes
  through ``reference._mm(mode, ...)``, so the control's precision
  reaches it.
* ``flops(c, T) -> float``: the forward model FLOPs of one sequence of
  ``T`` tokens (2 per multiply-add).  Attention counts only the
  query-key pairs its mask keeps (``flops.pairs``); recomputation counts
  nothing.
* optionally ``INIT``: ``{name: f(key, shape) -> float32 array}``, for
  leaves that are neither ``matrix`` nor ``zeros``.
* optionally ``attention(c) -> {"H", "KV", "hd", "window"}``: for a
  layer that makes one causal flash-attention call per sequence, its
  query and key-value heads, head size and sliding window (0: none).

``c`` is the configuration file's dict.
"""
from __future__ import annotations

import importlib

ENDS = ("embed", "head")      # the chain's ends, which are no module here


def load(kind: str):
    """The module of layer kind ``kind``."""
    return importlib.import_module(f"{__name__}.{kind}")


def declared(kind: str, name: str, default=None):
    """What kind ``kind``'s module defines as ``name`` (``INIT``,
    ``attention``), else ``default``; the chain's ends define nothing."""
    if kind in ENDS:
        return default
    return getattr(load(kind), name, default)
