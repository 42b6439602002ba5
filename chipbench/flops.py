"""Model FLOPs of one training step, from a configuration's sizes.

The operations the forward and backward passes require: matmul FLOPs
(2 per multiply-add) of every layer for one sequence, times 3 (the
backward pass is two matmuls per forward one), times the batch.  Nothing
recomputed counts, and attention counts only the query-key pairs its
mask keeps (``pairs``).  Elementwise work rides free.  The embedding and
the head are counted here; each kind of layer between them counts its
own (``chipbench/layers/<kind>.py``).

This is the arithmetic of the program's per-cut metadata
(``LMLayerStack.cut_meta``), written out again here so that the
yardstick does not move with the program; the program counts the full
``T x T`` attention scores, this file does not.
"""
from __future__ import annotations

from typing import Dict

from chipbench import layers


def dims(c: Dict) -> Dict:
    """The sizes the reference, the FLOP count and the kernels read, in
    one set of names, from a configuration file's keys.  ``window`` is
    the attention's sliding window (0: none)."""
    D = c["hidden_size"]
    H = c["num_attention_heads"]
    return {"D": D, "V": c["vocab_size"], "F": c["intermediate_size"],
            "H": H, "KV": c["num_key_value_heads"],
            "hd": c.get("head_dim") or D // H,
            "window": c.get("sliding_window") or 0,
            "layers": list(c["layers"])}


def pairs(T: int, S: int, window: int = 0) -> float:
    """Query-key pairs that causal attention of ``T`` queries over ``S``
    keys keeps: half of ``T x S``, and under a sliding window of ``w``
    keys (``0 < w < S``) ``T w - w^2 / 2``, which is ``T S / 2`` at
    ``w = S = T``."""
    if 0 < window < S:
        return float(T * window - window * window / 2)
    return T * S / 2


def head_fwd(d: Dict, T: int) -> float:
    return float(2 * T * d["D"] * d["V"])


def layer_flops(c: Dict, kind: str, T: int) -> float:
    """Forward model FLOPs of one layer of ``kind`` on one sequence: the
    embedding's lookup needs none, the head its projection to the
    vocabulary, and every other kind what its module says
    (``chipbench/layers/<kind>.py``)."""
    if kind == "embed":
        return 0.0
    if kind == "head":
        return head_fwd(dims(c), T)
    return layers.load(kind).flops(c, T)


def step_flops(c: Dict, T: int, B: int) -> float:
    """Model FLOPs of one training step on a batch of ``B`` sequences of
    ``T`` tokens."""
    return 3.0 * B * sum(layer_flops(c, k, T) for k in c["layers"])
