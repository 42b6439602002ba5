"""Where in the program each operation of a traced step came from.

A trace's operation event is named by its HLO instruction
(``trace.op_name``), which says nothing of the program's structure.
The program names its work with ``jax.named_scope`` (``repro.obs``):
each instruction of the compiled step carries an ``op_name`` such as
``jit(hiertrain_step)/transpose(jvp(hier.cloud))/layer3.attn/
flash_attention_bwd/while``.  ``scope_map`` reads instruction name ->
``op_name`` from the compiled text of the step that ran
(``step_program``), ``scope_of`` turns an ``op_name`` into a label
(``hier.cloud/layer3.attn/bwd/flash_attention_bwd``), and ``step_ops``
labels each operation of the traced window's step program runs.

A program without ``repro.obs`` names nothing: ``step_program`` then
returns None and every reader built on it reads nothing.
"""
from __future__ import annotations

import importlib
import re
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import trace

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_PARAM = re.compile(r"params\[(\d+)\]")
_OUTER = re.compile(r"layer\d+\.\w+|hier\.\w+|loss|reference")
_KERNEL = re.compile(r"(?:flash_attention|gla_scan)_(?:fwd|bwd)|int8_quant")
RELAYOUTS = ("copy", "bitcast", "transpose")
COVERED = re.compile(r"(?:^|/)(?:layer\d+\.\w+|hier\.\w+|loss)(?:/|$)")
UNSCOPED = "unscoped"
UNMAPPED = "unmapped"


def program_obs():
    """The program's ``repro.obs``, or None for a program without it.
    An import of it that fails for another reason raises."""
    try:
        return importlib.import_module("repro.obs")
    except ModuleNotFoundError as e:
        if e.name != "repro.obs":
            raise
        return None


def step_program() -> Optional[Tuple[str, Tuple, Tuple[int, ...]]]:
    """``(compiled text, arguments' shapes, token batch shape)`` of the
    step program the run dispatched last, from the program's
    ``repro.obs``; None where the program has none."""
    obs = program_obs()
    got = obs.last_step() if obs is not None else None
    if got is None:
        return None
    fn, args, tokens = got
    return fn.lower(*args).compile().as_text(), args, tokens


def scope_map(hlo_text: str, inherit: bool = True) -> Dict[str, str]:
    """Instruction name -> ``op_name``, from a compiled program's text
    (``compiled.as_text()``).  Instruction names are unique in a
    module; the trace's operation events carry them.  An ``op_name``
    that lists several (``a;b``) keeps the first.

    The compiler leaves some instructions without an ``op_name``.  With
    ``inherit``, such an instruction takes, in this order: the first
    ``op_name`` among the instructions it fuses (its root first); for a
    relayout (``copy``, ``bitcast``, ``transpose``), that of its first
    operand that has one (a parameter's relayout reads as the
    parameter's path); that of the instruction that calls its
    computation (a loop's body).  What is left reads ``""``."""
    comps: Dict[str, List[str]] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    cur: List[str] = []
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = _COMP.match(line)
            if head:
                cur = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line, m.end())
        own[name] = "" if op is None else \
            re.sub(r"\\(.)", r"\1", op.group(1)).split(";")[0]
        code = _OPCODE.search(line, m.end())
        calls[name] = _CALLS.findall(line)
        operands[name] = [o for o in _REF.findall(line, m.end())
                          if o not in calls[name]] \
            if code and code.group(1) in RELAYOUTS else []
        if line.lstrip().startswith("ROOT"):
            cur.insert(0, name)
        else:
            cur.append(name)
    if not inherit:
        return own
    out: Dict[str, str] = {}

    def resolve(name: str, depth: int = 0) -> str:
        if name in out:
            return out[name]
        op = own.get(name, "")
        if not op:
            op = next((own[i] for c in calls.get(name, ())
                       for i in comps.get(c, ()) if own[i]), "")
        if not op and depth < 8:
            op = next((r for o in operands.get(name, ()) if o in own
                       for r in [resolve(o, depth + 1)] if r), "")
        out[name] = op
        return op

    caller: Dict[str, str] = {}
    for name in own:
        op = resolve(name)
        for c in calls[name]:
            if op:
                caller.setdefault(c, op)
    for comp, names in comps.items():
        for name in names:
            if not out[name]:
                out[name] = caller.get(comp, "")
    return out


def scope_of(name: str) -> str:
    """The label of an ``op_name``: the program's named scopes in order
    (phase ``hier.*``, layer ``layer{i}.{kind}``, ``loss``), ``fwd`` or
    ``bwd`` where automatic differentiation made the operation (``jvp``
    / ``transpose``), then the kernel's scope.  An argument path such
    as ``params[4]['mlp']['w_down']`` (a parameter's relayout) reads
    ``layer4.params``; an ``op_name`` with none of these, ``unscoped``."""
    m = _PARAM.match(name)
    if m:
        return f"layer{m.group(1)}.params"
    parts = re.sub(r"(?:transpose|jvp)\(", "", name).replace(")", "")
    parts = parts.split("/")
    outer = [p for p in parts if _OUTER.fullmatch(p)]
    kernel = [p for p in parts if _KERNEL.fullmatch(p)]
    phase = ["bwd"] if "transpose(" in name else \
        ["fwd"] if "jvp(" in name else []
    label = "/".join(outer + (phase if outer or kernel else []) + kernel)
    return label or UNSCOPED


def step_ops(tr: trace.Trace, dev: int, scopes: Dict[str, str],
             min_ms: float = 1.0) -> List[Tuple[trace.Event, str]]:
    """Each operation of the traced window's step program runs on
    ``dev`` (``trace.window``: the runs of the first step program), with
    the label of its ``op_name``; ``unmapped`` where the scope map lacks
    its instruction.  The eager programs between steps are left out."""
    mods = trace.step_modules(tr, dev, min_ms)
    runs = [(m.start, m.end) for m in mods if m.name == mods[0].name]
    if not runs:
        return []
    out, i = [], 0
    for e in tr.ops.get(dev, []):
        while i < len(runs) and runs[i][1] < e.start:
            i += 1
        if i < len(runs) and runs[i][0] <= e.start and e.end <= runs[i][1]:
            op = scopes.get(trace.op_name(e.name))
            out.append((e, UNMAPPED if op is None else scope_of(op)))
    return out


def scope_time(ops: List[Tuple[trace.Event, str]],
               pred: Callable[[str, str], bool]) -> float:
    """Device seconds of the operations for which ``pred(instruction
    name, label)`` holds: the union of their intervals, so a loop and
    the operations inside it count once."""
    return trace.total(trace.union(
        [(e.start, e.end) for e, label in ops
         if pred(trace.op_name(e.name), label)])) / 1e9
