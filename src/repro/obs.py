"""The program's spans, scopes and compile records, in one place.

* :func:`span` — a host span, ``jax.profiler.TraceAnnotation`` named
  ``hiertrain.<name>``.  It lands in the profiler's own trace when one
  is running and costs next to nothing when none is; the profiler trace
  is the only exporter.
* :func:`step_span` — the same, as a ``StepTraceAnnotation`` with the
  step number (``hiertrain.step``): the profiler's tools delimit
  training steps by it.
* :func:`scope` — ``jax.named_scope``: acts while a jitted function is
  traced and names every operation made inside it (the compiled
  program's ``op_name`` metadata), at no cost at run time.  Jitted code
  calls only this.
* :data:`COMPILES` — for the step program (:data:`STEP_PROGRAM`), one
  record per compile of it: the seconds of each of JAX's compile events
  (tracing, lowering, and the backend compile or the load from JAX's
  compile cache), by the event's name.  One ``jax.monitoring`` listener,
  registered at import, keeps it; :func:`snapshot` and :func:`reset`
  read and clear it.
* :data:`BLOCKS` — per kernel that skips score blocks outside its
  mask, one ``(visited, total)`` pair per traced call: the blocks its
  grid works on and all ``T/bq x S/bk`` of them.  Static per shape, so
  it is recorded while the call is traced; :func:`count_blocks` adds
  one, :func:`blocks` reads them.
* :func:`note_step` / :func:`last_step` — the step program last
  dispatched, its arguments' shapes and its token batch's shape, so
  that the compiled text of what ran (each instruction's ``op_name``)
  can be read after it ran, in the same process, where JAX's
  in-memory cache serves the executable that ran.

The jitted step programs carry stable names (:data:`STEP_PROGRAM`,
:data:`REFERENCE_PROGRAM`): the compiled modules, their runs in a
profiler trace and their compile events carry them, which is how the
step's compile events are told from those of every other program.
"""
from __future__ import annotations

import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

PREFIX = "hiertrain."
STEP_PROGRAM = "hiertrain_step"
REFERENCE_PROGRAM = "hiertrain_reference_step"

COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name)


def step_span(step: int) -> jax.profiler.StepTraceAnnotation:
    return jax.profiler.StepTraceAnnotation(PREFIX + "step", step_num=step)


def scope(name: str):
    return jax.named_scope(name)


def program_of(fun_name: str) -> Optional[str]:
    """:data:`STEP_PROGRAM` for a compile event of the step
    (``hiertrain_step`` while traced, ``jit(hiertrain_step)`` while
    lowered and compiled), None for one of any other program."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    return STEP_PROGRAM if fun_name == STEP_PROGRAM else None


class Compiles:
    """Per recorded program, one ``{event name: seconds}`` record per
    compile; a record closes with the program's backend compile event.
    The compile-cache events carry no program name and happen inside a
    backend compile: they go to the program whose event came last."""

    def __init__(self) -> None:
        self.done: Dict[str, List[Dict[str, float]]] = {}
        self._open: Dict[str, Dict[str, float]] = {}
        self._last: Optional[str] = None

    def on_duration(self, event: str, secs: float, fun_name: str = "",
                    **_) -> None:
        if not event.startswith(COMPILE_EVENTS):
            return
        if fun_name:
            self._last = program_of(fun_name)
        prog = self._last
        if prog is None:
            return
        rec = self._open.setdefault(prog, {})
        rec[event] = rec.get(event, 0.0) + secs
        if event == BACKEND_COMPILE_EVENT:
            self.done.setdefault(prog, []).append(self._open.pop(prog))


COMPILES = Compiles()
jax.monitoring.register_event_duration_secs_listener(COMPILES.on_duration)


def snapshot() -> Dict[str, List[Dict[str, float]]]:
    """The recorded programs' compile records so far, oldest first."""
    return {p: [dict(r) for r in recs] for p, recs in COMPILES.done.items()}


def reset() -> None:
    COMPILES.done.clear()
    COMPILES._open.clear()
    COMPILES._last = None


BLOCKS: Dict[str, List[Tuple[int, int]]] = {}


def count_blocks(kernel: str, visited: int, total: int) -> None:
    BLOCKS.setdefault(kernel, []).append((visited, total))


def blocks() -> Dict[str, List[Tuple[int, int]]]:
    """Per kernel, the ``(visited, total)`` score blocks of each call
    traced so far, oldest first."""
    return {k: list(v) for k, v in BLOCKS.items()}


# The step program last dispatched: a weak reference to the jitted
# function, its arguments' shapes and the token batch's shape.
_STEP: List[Any] = []


def _shape_of(a) -> jax.ShapeDtypeStruct:
    # Lowered with the sharding the call had (a committed array's, none
    # for an uncommitted one), JAX serves the executable that ran from
    # its in-memory cache, and the lowering is the same program.
    keep = getattr(a, "committed", False)
    return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                sharding=a.sharding if keep else None)


def note_step(fn: Callable, args: Tuple, tokens: Tuple[int, ...]) -> None:
    """Remember the jitted step ``fn``, the shapes of ``args`` it was
    called with, and the shape of the token batch those hold."""
    _STEP[:] = [weakref.ref(fn), jax.tree.map(_shape_of, args),
                tuple(tokens)]


def last_step() -> Optional[Tuple[Callable, Tuple, Tuple[int, ...]]]:
    """``(jitted step, its arguments' shapes, token batch shape)`` of
    the step last dispatched; None where none was or its program is
    gone.  ``fn.lower(*args).compile().as_text()`` is its compiled
    text."""
    fn = _STEP[0]() if _STEP else None
    return None if fn is None else (fn, _STEP[1], _STEP[2])
