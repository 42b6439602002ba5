"""Flash-attention forward (the program's ``kernels/flash_attention.py``).

Its Pallas call takes q [BH,T,hd], k and v [BKV,S,hd] and returns
o [BH,T,hd] and the log-sum-exp [BH,T,128] (f32, lane-wide).  The trace
names the call only by its operands, so it is matched by that
signature.  Every attention the benchmark runs is causal, under the
configuration's sliding window where it has one: the call needs 4*hd
FLOPs per query-key pair the mask keeps (``flops.pairs``), and reads q,
k, v and writes o and one f32 log-sum-exp per query.
"""
from chipbench.flops import pairs
from chipbench.trace import BYTES, call_shapes


def match(name: str) -> bool:
    outs, ins = call_shapes(name)
    return (len(ins) == 3 and len(outs) == 2 and outs[1][0] == "f32"
            and len(outs[1][1]) == 3 and outs[1][1][-1] == 128
            and ins[0][1] == outs[0][1])


def cost(name: str, dims):
    outs, ins = call_shapes(name)
    (tq, (BH, T, hd)), (tk, (BKV, S, _)) = ins[0], ins[1]
    flops = 4.0 * BH * pairs(T, S, dims.get("window", 0)) * hd
    nbytes = (BH * T * hd * (BYTES[tq] + BYTES[outs[0][0]])
              + 2 * BKV * S * hd * BYTES[tk] + BH * T * 4)
    return flops, float(nbytes)
