"""Model FLOPs of one training step, from a configuration's sizes.

The operations the forward and backward passes require: matmul FLOPs
(2 per multiply-add) of every layer for one sequence, times 3 (the
backward pass is two matmuls per forward one), times the batch.  Nothing
recomputed counts, and attention counts only the query-key pairs its
mask keeps (``pairs``).  Elementwise work rides free.

This is the arithmetic of the program's per-cut metadata
(``LMLayerStack.cut_meta``), written out again here so that the
yardstick does not move with the program; the program counts the full
``T x T`` attention scores, this file does not.
"""
from __future__ import annotations

from typing import Dict


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


def attn_fwd(d: Dict, T: int) -> float:
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    proj = 2 * T * D * (H * hd) * 2 + 2 * T * D * (KV * hd) * 2
    scores = 4 * pairs(T, T, d["window"]) * H * hd     # QK^T and AV
    return float(proj + scores + 3 * 2 * T * D * F)


def head_fwd(d: Dict, T: int) -> float:
    return float(2 * T * d["D"] * d["V"])


FWD = {"embed": lambda d, T: 0.0, "attn": attn_fwd, "head": head_fwd}


def step_flops(c: Dict, T: int, B: int) -> float:
    """Model FLOPs of one training step on a batch of ``B`` sequences of
    ``T`` tokens."""
    d = dims(c)
    return 3.0 * B * sum(FWD[k](d, T) for k in d["layers"])
