"""The trace reduction, on a trace recorded on a TPU v5e: two runs each
of a flash-attention call (q, k, v [8,512,128] bf16) and a 1024^2 bf16
matmul, with the benchmark's host spans around them."""
import os

import pytest

from chipbench import trace
from chipbench.kernels import flash_attention_fwd

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
MIN_MS = 0.01          # this trace's programs run for microseconds


@pytest.fixture(scope="module")
def tr():
    return trace.load(DATA)


def test_planes_lines_and_spans(tr):
    assert sorted(tr.ops) == [0] and len(tr.ops[0]) == 12
    assert len(tr.modules[0]) == 4
    assert [s.name for s in tr.spans] == [
        "bench.window", "bench.dispatch", "bench.block", "bench.dispatch",
        "bench.block"]


def test_window_is_the_first_program_and_busy_inside_it(tr):
    w = trace.window(tr, 0, MIN_MS)
    first = [m for m in tr.modules[0] if m.name == tr.modules[0][0].name]
    assert w.lo == first[0].start and w.hi == first[-1].end
    assert 0 < w.busy_ns < w.length_ns
    assert trace.window(tr, 0) is None          # no step runs 1 ms or more


def test_idle_gaps_are_labelled_by_host_spans(tr):
    w = trace.window(tr, 0, MIN_MS)
    gaps = trace.idle_gaps(tr, w, min_ms=MIN_MS)
    total = sum(s for _, s in gaps)
    assert gaps and abs(total - (w.length_ns - w.busy_ns) / 1e9) < 1e-9 \
        or len(gaps) == 10
    assert {name for name, _ in gaps} <= {s.name for s in tr.spans} | \
        {"none"}
    assert trace.host_offset(tr, 0, MIN_MS) < 0   # device clock runs behind


def test_top_ops_and_kernel_match(tr):
    w = trace.window(tr, 0, MIN_MS)
    ops = trace.top_ops(tr, w)
    assert ops[0][1] >= ops[-1][1] > 0
    calls = [e for e in tr.ops[0] if flash_attention_fwd.match(e.name)]
    assert len(calls) == 2
    f, b = flash_attention_fwd.cost(calls[0].name, {})
    assert f == 2.0 * 8 * 512 * 512 * 128                 # causal half
    assert b == 8 * 512 * 128 * (2 + 2) + 2 * 8 * 512 * 128 * 2 + 8 * 512 * 4


@pytest.mark.parametrize("a,b,want", [
    ([(0, 2), (1, 3), (5, 6)], [(2.5, 5.5)], [(0, 2.5), (5.5, 6)]),
    ([(0, 10)], [(1, 2), (3, 4)], [(0, 1), (2, 3), (4, 10)]),
    ([(0, 1)], [], [(0, 1)]),
])
def test_interval_arithmetic(a, b, want):
    assert trace.subtract(a, b) == want
    assert trace.total(trace.union(a)) >= trace.total(want)
