"""Chunked gated-linear-recurrence forward (the program's
``kernels/gla_scan.py``): the SSD scan of every Mamba2 layer.

Its Pallas call takes q and k [BH,T,dk], v [BH,T,dv], the chunk-local
cumulative log-decay twice at lane width (``[BH,T,128]`` and
``[BH,8,T]``, f32) and each chunk's total as one ``[8, TL]`` tile per
chunk (``[BH,nc*8,TL]``, f32); it returns y [BH,T,dv], the final state
[BH,dk,dv] and the normalizer [BH,dk,128] (both f32).  The trace names
the call only by its operands, so it is matched by that signature, and
the chunk comes from them too: ``nc`` = the total's rows over 8, ``W =
T / nc``.

The algorithm per head: inside each chunk the causal pairs ``j <= i``
(``W (W + 1) / 2`` of them), ``2 dk`` FLOPs for the score and ``2 dv``
for its share of y each; per position ``4 dk dv`` to build the chunk's
state and to query the carried one.  Bytes: q, k and v read, y written,
one f32 log-decay per position and the f32 state written; the
lane-replicated copies of the decay are the kernel's layout, not the
algorithm's need.
"""
from chipbench.trace import BYTES, call_shapes

SUBLANES = 8


def scan_flops(T: int, W: int, dk: int, dv: int) -> float:
    """FLOPs of one head's chunked scan over ``T`` positions in chunks of
    ``W``."""
    kept = W * (W + 1) // 2
    return float((T // W) * kept * (2 * dk + 2 * dv) + T * 4 * dk * dv)


def match(name: str) -> bool:
    outs, ins = call_shapes(name)
    return (len(ins) == 6 and len(outs) == 3
            and all(t == "f32" for t, _ in ins[3:])
            and len(ins[3][1]) == 3 and ins[3][1][-1] == 128
            and len(ins[4][1]) == 3 and ins[4][1][1] == SUBLANES
            and outs[1][0] == "f32" and ins[2][1] == outs[0][1])


def cost(name: str, dims):
    outs, ins = call_shapes(name)
    (tq, (BH, T, dk)), (tk, _), (tv, (_, _, dv)) = ins[:3]
    W = T // (ins[5][1][1] // SUBLANES)
    flops = BH * scan_flops(T, W, dk, dv)
    nbytes = BH * T * (dk * (BYTES[tq] + BYTES[tk]) + dv * BYTES[tv]
                       + dv * BYTES[outs[0][0]] + 4) + BH * dk * dv * 4
    return flops, float(nbytes)
