"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``jax.profiler.ProfileData`` reads the file.  Each TPU is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
run on the device (the event's name is the HLO instruction's text, so a
kernel's operand shapes can be read from it), and its ``XLA Modules``
line one event per program run.  The host plane ``/host:CPU`` has a
``python`` line that holds the benchmark's own spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation``).

The device and host clocks of one trace are not aligned exactly.  The
device timeline is used alone for busy and idle time.  Host spans are
put on the device clock only to label idle gaps, with the offset that
makes no step program start before the host dispatched it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

@dataclasses.dataclass
class Event:
    name: str
    start: float                         # ns
    dur: float                           # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]          # device id -> XLA Ops events
    modules: Dict[int, List[Event]]      # device id -> XLA Modules events
    spans: List[Event]                   # benchmark host spans (bench.*)


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[dev] = sorted(evs, key=lambda e: e.start)
                elif line.name == "XLA Modules":
                    modules[dev] = sorted(evs, key=lambda e: e.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [Event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name.startswith("bench.")]
    return Trace(ops, modules, sorted(spans, key=lambda e: e.start))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union of ``a`` not covered by the union of ``b``."""
    out: List[Interval] = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


@dataclasses.dataclass
class Window:
    """The traced steps on one device: from the start of the first step
    program to the end of the last."""
    device: int
    lo: float
    hi: float
    busy: List[Interval]

    @property
    def length_ns(self) -> float:
        return self.hi - self.lo

    @property
    def busy_ns(self) -> float:
        return total(self.busy)


def step_modules(tr: Trace, dev: int, min_ms: float = 1.0) -> List[Event]:
    """The device's program runs that are steps: those longer than
    ``min_ms`` (the benchmark's own small programs take microseconds)."""
    return [m for m in tr.modules.get(dev, []) if m.dur >= min_ms * 1e6]


def window(tr: Trace, dev: int, min_ms: float = 1.0) -> Optional[Window]:
    """The runs of the first step program in the trace (a cell that
    traces a second program after it, as the split cell's reference
    step, keeps that one out)."""
    mods = step_modules(tr, dev, min_ms)
    if not mods:
        return None
    mods = [m for m in mods if m.name == mods[0].name]
    lo, hi = mods[0].start, max(m.end for m in mods)
    busy = clip(union([(e.start, e.end) for e in tr.ops.get(dev, [])]),
                lo, hi)
    return Window(dev, lo, hi, busy)


def host_offset(tr: Trace, dev: int, min_ms: float = 1.0) -> float:
    """Offset to add to host times to put them on ``dev``'s clock: the
    smallest that lets no step program start before its dispatch span
    began (``bench.dispatch``, in order)."""
    mods = step_modules(tr, dev, min_ms)
    disp = [s for s in tr.spans if s.name == "bench.dispatch"]
    pairs = list(zip(disp, mods))
    if not pairs:
        return 0.0
    return min(m.start - d.start for d, m in pairs)


def idle_gaps(tr: Trace, w: Window, top: int = 10, min_ms: float = 1.0
              ) -> List[Tuple[str, float]]:
    """The longest idle gaps inside the window, each labelled by the
    innermost benchmark span open on the host at its midpoint."""
    gaps = subtract([(w.lo, w.hi)], w.busy)
    off = host_offset(tr, w.device, min_ms)
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2 - off        # on the host's clock
        open_ = [sp for sp in tr.spans if sp.start <= mid < sp.end]
        label = min(open_, key=lambda sp: sp.dur).name if open_ else "none"
        out.append((label, (e - s) / 1e9))
    return out


def top_ops(tr: Trace, w: Window, top: int = 10) -> List[Tuple[str, float]]:
    """Device operations by total time inside the window."""
    agg: Dict[str, float] = {}
    for e in tr.ops.get(w.device, []):
        if e.start >= w.lo and e.end <= w.hi:
            k = op_name(e.name)
            agg[k] = agg.get(k, 0.0) + e.dur
    return [(k, v / 1e9) for k, v in
            sorted(agg.items(), key=lambda kv: -kv[1])[:top]]


SHAPE = re.compile(r"\b(bf16|f32|f16|s32|s8|u8|f8e4m3fn|f8e5m2|pred)"
                   r"\[([\d,]*)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1, "u8": 1,
         "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1}


def call_shapes(event_name: str) -> Tuple[List[Tuple[str, Tuple[int, ...]]],
                                          List[Tuple[str, Tuple[int, ...]]]]:
    """(outputs, operands) of a custom call, as (dtype, shape) pairs read
    from the instruction's text."""
    if " custom-call(" not in event_name:
        return [], []
    lhs, rest = event_name.split(" custom-call(", 1)
    operands = rest.split("custom_call_target=", 1)[0]

    def shapes(s):
        return [(t, tuple(int(x) for x in dims.split(",") if x))
                for t, dims in SHAPE.findall(s)]
    return shapes(lhs.split(" = ", 1)[-1]), shapes(operands)
