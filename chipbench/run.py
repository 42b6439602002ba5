"""Chip benchmark: one run of one cell.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout.  A cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``chipbench/configs/<name>.json``)
and a traffic mix (``chipbench/mixes/<name>.json``); its comparison
limits are in ``chipbench/limits/<cell>.json`` and each per-layer metric
is read by ``chipbench/metrics/<metric>.py``.

One run, in one process:

1. Set-up: put ``src`` on the path, keep JAX's compile cache in
   ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``, refuse
   any platform but ``tpu`` or fewer chips than the cell asks for; plan
   the fleet (``api.plan``; a pinned schedule replaces the planner's
   pick), build ``Plan.step_fn`` (with ``cloud_mesh=`` on four chips),
   make the weights and a pool of distinct token batches on the device
   from the seed, and drive the step through its first three steps on
   the pool's first batches.  Those steps compile the step program and
   give the readings that the correctness comparison needs.
2. ``--trace 0``: the window.  Steps run back to back on the pool's
   next batches for ``--seconds`` seconds, the host waiting only for
   the step before the one it has just dispatched, then for the last.
   ``tokens_per_s`` is every token of every step over the window's wall
   time; ``setup_s`` runs from process start to the first timed step.
   ``--trace 1``: a few steps under the profiler instead, reduced to the
   per-layer metrics.
3. The peak device memory is read, the program's state freed, and the
   plain reference (``chipbench/reference.py``) retraces the first steps
   from the same weights and batches.  ``correct`` is whether every
   compared number is within its limit.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit); the same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARM_STEPS = 3           # steps driven in set-up and retraced by the reference
POOL = 64                # distinct batches made per run
TRACE_STEPS = 4          # steps under the profiler with --trace 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict            # configs/<config>.json
    mix: Dict               # mixes/<traffic>.json
    limits: Dict            # limits/<cell>.json
    per_layer: List[Dict]   # BENCHMARK.json's per-layer metrics it reports


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(root, cfg["file"]),
        mix=load_json(root, "chipbench", "mixes", f"{w['traffic']}.json"),
        limits=load_json(root, "chipbench", "limits", f"{name}.json"),
        per_layer=[m for m in bench["per_layer"] if mine(m)])


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class Spans:
    """The benchmark's host spans: host-clock seconds per name, and a
    ``TraceAnnotation`` of the same name while the profiler runs.  It
    also counts the host's garbage collections (span ``gc``, by
    generation) and JAX's compile and compile-cache events, so that a
    stall or a compile inside the window can be named."""

    def __init__(self):
        import jax
        self.seconds: Dict[str, float] = {}
        self.tracing = False
        self.gc_runs = [0, 0, 0]
        self.compile_events = 0
        self._gc_open = None
        gc.callbacks.append(self._gc)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = jax.profiler.TraceAnnotation(f"bench.{name}") \
            if self.tracing else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + \
            time.perf_counter() - t0

    def _gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_open = self("gc")
            self._gc_open.__enter__()
        elif self._gc_open is not None:
            self._gc_open.__exit__(None, None, None)
            self._gc_open = None
            self.gc_runs[info["generation"]] += 1

    def _event(self, name: str, secs: float, **_) -> None:
        if name.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
            self.compile_events += 1

    def close(self) -> None:
        import jax
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)
            jax.monitoring.unregister_event_duration_listener(self._event)

    def snapshot(self) -> Dict[str, Any]:
        return {"gc_runs": list(self.gc_runs),
                "gc_s": self.seconds.get("gc", 0.0),
                "compile_events": self.compile_events}


def bootstrap(chips: int, require_tpu: bool = True):
    """The jax module, with the compile cache set and the chips checked."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chipbench: no program under {src}; run from a "
                         "checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    if require_tpu:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise SystemExit(f"chipbench: needs a TPU; found platform "
                             f"{devs[0].platform!r} ({devs[0].device_kind})")
        if len(devs) < chips:
            raise SystemExit(f"chipbench: the cell needs {chips} chips; "
                             f"found {len(devs)}")
    return jax


def peaks_of(kind: str) -> Dict:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         "chipbench/peaks.json")
    return table[kind]


def program_stack(cell: Cell):
    """The program's layer stack for the configuration, as it is run."""
    import importlib
    import jax.numpy as jnp
    from repro.models.lm.layerstack import lm_layerstack
    prog = cell.config["program"]
    mod, attr = prog["preset"].split(":")
    cfg = getattr(importlib.import_module(mod), attr)
    over = dict(prog.get("overrides", {}))
    if "dtype" in over:
        over["dtype"] = getattr(jnp, over["dtype"])
    return lm_layerstack(cfg.variant(**over), seq_len=cell.mix["seq_len"],
                         backend=cell.mix["backend"])


def make_fleet(spec: Dict):
    from repro.api import Fleet
    if "preset" in spec:
        return getattr(Fleet, spec["preset"])(**spec.get("args", {}))
    from repro.core import profiler
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in spec.items() if k != "workers"}
    return Fleet(workers=getattr(profiler, spec["workers"]), **kw)


def build_step(jax, cell: Cell, stack, spans: Spans):
    """``(plan, step, mesh)``: the plan for the cell's fleet and batch,
    its schedule pinned where the mix says so, and ``Plan.step_fn``."""
    import dataclasses as dc
    from repro.api import plan
    mix = cell.mix
    with spans("plan"):
        p = plan(stack, make_fleet(mix["fleet"]), mix["batch"],
                 wire=mix.get("wire"))
    if mix["schedule"] != "planned":
        from repro.core.cost_model import MultiSchedule
        s = dict(mix["schedule"])
        sched = MultiSchedule(**{k: tuple(v) if isinstance(v, list) else v
                                 for k, v in s.items()})
        p = dc.replace(p, result=dc.replace(p.result, schedule=sched))
    mesh = None
    if mix.get("cloud_mesh"):
        import numpy as np
        from jax.sharding import AxisType, Mesh
        n = mix["cloud_mesh"]["chips"]
        mesh = Mesh(np.array(jax.devices()[:n]), (mix["cloud_mesh"]["axis"],),
                    axis_types=(AxisType.Auto,))
    return p, p.step_fn(lr=mix["lr"], cloud_mesh=mesh), mesh


def placement(jax, mesh):
    """Where the run's arrays live: replicated over the mesh, or chip 0."""
    if mesh is None:
        return jax.sharding.SingleDeviceSharding(jax.devices()[0])
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


def make_pool(jax, seed: int, vocab: int, B: int, T: int, n: int, sharding):
    """``n`` distinct batches of uniform token ids and labels, made on the
    device in one call."""
    import jax.numpy as jnp
    from chipbench.reference import _key

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(jax.random.fold_in(key, 0x9E37))
        return (jax.random.randint(kx, (n, B, T), 0, vocab, jnp.int32),
                jax.random.randint(ky, (n, B, T), 0, vocab, jnp.int32))
    xs, ys = make(_key(seed))
    return [(jax.device_put(xs[i], sharding), jax.device_put(ys[i], sharding))
            for i in range(n)]


def check_layout(jax, stack, weights) -> None:
    shapes = jax.eval_shape(stack.init, jax.random.PRNGKey(0))
    got = [(a.shape, a.dtype) for a in jax.tree.leaves(weights)]
    want = [(a.shape, a.dtype) for a in jax.tree.leaves(shapes)]
    if jax.tree.structure(shapes) != jax.tree.structure(weights) or \
            got != want:
        raise SystemExit("chipbench: the configuration's weights do not "
                         "match the program's parameter layout")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def warm_steps(jax, cell: Cell, step, params, seed: int, pool,
               spans: Spans):
    """Drive the step from the seed's weights ``params`` through
    ``WARM_STEPS`` steps on the pool's first batches.  Returns (params,
    readings): each step's loss, per leaf the first step's applied
    change over ``lr`` (the gradient as the optimizer got it) and the
    change after the last."""
    from chipbench import reference as R
    lr = cell.mix["lr"]
    with spans("warm"):
        if R.change_norms(cell.config, params, seed).any():
            raise SystemExit("chipbench: the seed's weights made again "
                             "differ from the first making")
    losses = []
    grad1 = None
    for i in range(WARM_STEPS):
        with spans("compile" if i == 0 else "warm"):
            params, loss = step(params, *pool[i])
        with spans("warm"):
            losses.append(float(jax.device_get(loss)))
            if i == 0:
                grad1 = R.change_norms(cell.config, params, seed) / lr
    with spans("warm"):
        change = R.change_norms(cell.config, params, seed)
    return params, {"loss": losses, "grad1": grad1, "change": change}


def drive(jax, step, params, pool, spans: Spans, start: int,
          seconds: Optional[float] = None, steps: Optional[int] = None,
          marks: Optional[List[float]] = None):
    """Steps back to back on the pool's batches from ``start``, for
    ``steps`` steps or until ``seconds`` have passed: the host keeps at
    most two in flight (it waits for the previous step's loss after
    dispatching the next).  ``marks`` gets the host-clock time at which
    each step was seen done.  Returns (params, steps, elapsed seconds,
    losses)."""
    losses = []
    marks = [] if marks is None else marks
    i = 0
    with spans("window"):
        t0 = time.perf_counter()
        while True:
            with spans("dispatch"):
                params, loss = step(params, *pool[(start + i) % len(pool)])
            losses.append(loss)
            if i:
                with spans("sync"):
                    losses[i - 1].block_until_ready()
                marks.append(time.perf_counter() - t0)
            i += 1
            if i == steps or (seconds is not None and
                              time.perf_counter() - t0 >= seconds):
                break
        with spans("block"):
            jax.block_until_ready((params, losses[-1]))
        elapsed = time.perf_counter() - t0
    marks.append(elapsed)
    return params, i, elapsed, losses


def log_host(cell: Cell, marks: List[float], before: Dict,
             after: Dict) -> None:
    """What the host saw in a window: when each step was seen done, the
    garbage collections and the compile events in it."""
    shown = [round(m, 4) for m in marks[:32]]
    log(f"[{cell.name}] window: steps seen done at (s) {shown}"
        f"{' ...' if len(marks) > 32 else ''}; collections by generation "
        f"{[a - b for a, b in zip(after['gc_runs'], before['gc_runs'])]} "
        f"taking {after['gc_s'] - before['gc_s']:.4f} s; compile events "
        f"{after['compile_events'] - before['compile_events']}")


def nonfinite_count(jax, losses) -> int:
    return sum(1 for v in jax.device_get(losses) if not math.isfinite(float(v)))


def compare(prog: Dict, ref: Dict, limits: Dict) -> Dict[str, Dict]:
    """Each compared number beside its limit.

    ``loss``: the largest relative gap of a step's loss.  ``grad``: over
    the leaves, the largest gap between the program's and the
    reference's norm of the first step's gradient as the optimizer got
    it, over the larger of the reference leaf's norm and the median
    leaf's.  ``change``: the same for the change after the last step,
    over the leaves whose reference gradient is at least a thousandth of
    the median leaf's (the others move by round-off alone)."""
    import numpy as np

    def worst(a, b, keep=None):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if keep is not None:
            a, b = a[keep], b[keep]
        scale = np.maximum(b, np.median(b))
        gap = np.abs(a - b) / scale
        return float(np.max(gap)) if gap.size else float("nan"), \
            int(np.argmax(gap)) if gap.size else -1

    lp, lr_ = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    loss = float(np.max(np.abs(lp - lr_) / np.abs(lr_)))
    g, gi = worst(prog["grad1"], ref["grad1"])
    g_ref = np.asarray(ref["grad_exact"])
    keep = np.nonzero(g_ref >= 1e-3 * np.median(g_ref))[0]
    c, ci = worst(prog["change"], ref["change"], keep)
    out = {"loss": {"value": loss, "limit": limits["loss"]},
           "grad": {"value": g, "limit": limits["grad"], "leaf": gi},
           "change": {"value": c, "limit": limits["change"],
                      "leaf": int(keep[ci]) if ci >= 0 else -1}}
    return out


def reference_readings(jax, cell: Cell, seed: int, pool,
                       steps: int = WARM_STEPS, mode: str = "f32",
                       fault: Optional[str] = None) -> Dict:
    """The plain reference's readings on the first batches of the pool,
    on chip 0, from the seed's weights (made again).  ``fault`` plants
    one of the faults the comparison must catch, in the reference put
    in the program's place (see ``faults``)."""
    from chipbench import reference as R
    c, lr = cell.config, cell.mix["lr"]
    dev = jax.devices()[0]
    params = R.make_weights(c, seed)
    losses = []
    grad1 = grad_exact = None
    for i in range(steps):
        x, y = (jax.device_put(a, dev) for a in pool[i])
        kw = faults(cell, fault, x.shape[0])
        params, loss, gn, dn = R.sgd_step(c, params, x, y, lr, mode=mode,
                                          **kw)
        losses.append(loss)
        if i == 0:
            grad1, grad_exact = dn / lr, gn
    change = R.change_norms(c, params, seed)
    del params
    return {"loss": losses, "grad1": grad1, "grad_exact": grad_exact,
            "change": change}


def faults(cell: Cell, fault: Optional[str], B: int) -> Dict:
    """Arguments of ``reference.sgd_step`` that plant a fault:

    * ``half_batch``: half of the batch left out, the mean taken over the
      rest;
    * ``no_exchange`` (a cell with ``cloud_mesh``): the sum over the
      data-parallel shards left out, so the update and loss are the
      first shard's alone (over ``B``);
    * ``drop_stream``: the front segment's gradient from the device
      stream left out (the split cell's merge without its exchange)."""
    if fault is None:
        return {}
    n = len(cell.config["layers"])
    if fault == "half_batch":
        rows = list(range(B // 2))
        return {"grad_rows": [rows] * n, "loss_rows": rows,
                "divisor": B // 2}
    if fault == "no_exchange":
        rows = list(range(B // cell.mix["cloud_mesh"]["chips"]))
        return {"grad_rows": [rows] * n, "loss_rows": rows, "divisor": B}
    if fault == "drop_stream":
        s = cell.mix["schedule"]
        o_rows, cut = list(range(s["b_o"])), max(s["m_s"])
        return {"grad_rows": [o_rows if i < cut else list(range(B))
                              for i in range(n)]}
    raise ValueError(f"unknown fault {fault!r}")


def device_info(jax, chips: int) -> Dict:
    devs = jax.devices()[:chips]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    # The TPU runtime keeps a program's temporaries in a reserved region
    # that ``peak_bytes_in_use`` leaves out: the peak is the sum.
    stats = [d.memory_stats() or {} for d in devs]
    info["memory_peak_bytes"] = int(max(
        s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        for s in stats))
    return info


def _finite_or_none(x):
    """JSON has no NaN: a number that is not finite prints as null."""
    if isinstance(x, dict):
        return {k: _finite_or_none(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite_or_none(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, out=sys.stdout) -> Dict:
    jax = bootstrap(cell.chips, require_tpu)
    from chipbench import flops as F
    from chipbench import reference as R
    spans = Spans()
    kind = jax.devices()[0].device_kind
    peaks = peaks_of(kind) if require_tpu else {"bf16_flops_per_s": 1.0,
                                                "hbm_bytes_per_s": 1.0}
    mix = cell.mix
    B, T = mix["batch"], mix["seq_len"]
    stack = program_stack(cell)
    p, step, mesh = build_step(jax, cell, stack, spans)
    log(f"[{cell.name}] {stack.name} B={B} T={T} chips={cell.chips} "
        f"schedule {p.schedule.describe()}")
    where = placement(jax, mesh)
    with spans("compile"):
        params = R.make_weights(cell.config, seed, where)
        check_layout(jax, stack, params)
        pool = make_pool(jax, seed, cell.config["vocab_size"], B, T, POOL,
                         where)
    params, prog = warm_steps(jax, cell, step, params, seed, pool, spans)

    rec: Dict[str, Any] = {"chips": cell.chips, "peaks": peaks,
                           "config": cell.config,
                           "dims": F.dims(cell.config),
                           "flops_per_step": F.step_flops(cell.config, T, B)}
    result: Dict[str, Any] = {}
    # Set-up ends with a full collection, and what it made is frozen out
    # of later ones: a collection in the window scans the window's
    # objects alone.
    gc.collect()
    gc.freeze()
    if not trace:
        setup_s = time.perf_counter() - T_START
        before, marks = spans.snapshot(), []
        params, n, elapsed, losses = drive(jax, step, params, pool, spans,
                                           WARM_STEPS, seconds=seconds,
                                           marks=marks)
        after = spans.snapshot()
        metrics = {"tokens_per_s": {"value": n * B * T / elapsed,
                                    "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        log(f"[{cell.name}] window: {n} steps in {elapsed:.4f} s; set-up "
            f"{setup_s:.3f} s")
        log_host(cell, marks, before, after)
    else:
        params, n, losses, tr = traced_window(jax, cell, step, stack, params,
                                              pool, spans, rec)
        metrics = {}
    device = device_info(jax, cell.chips)
    failed = nonfinite_count(jax, losses)
    del params
    rec["memory_peak_bytes"] = device["memory_peak_bytes"]
    rec["spans"] = {"plan": spans.seconds.get("plan"),
                    "compile": spans.seconds.get("compile")}
    if trace:
        from chipbench import metrics as M
        from chipbench import trace as trace_mod
        for m in cell.per_layer:
            v = M.load(m["name"]).read(rec, tr)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        ws = [w for w in (trace_mod.window(tr, d) for d in
                          range(cell.chips)) if w]
        device["busy_s"] = sum(w.busy_ns for w in ws) / len(ws) / 1e9
        device["window_s"] = sum(w.length_ns for w in ws) / len(ws) / 1e9
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace_mod.top_ops(tr, ws[0])],
            "idle_gaps": [list(x) for x in trace_mod.idle_gaps(tr, ws[0])]}

    # -- correctness: the program's state is gone; the reference runs ----
    from repro.core import hybrid_step as hs
    hs.clear_jit_cache()
    del step, p
    gc.collect()
    jax.clear_caches()
    gc.unfreeze()
    spans.close()
    t0 = time.perf_counter()
    ref = reference_readings(jax, cell, seed, pool)
    checks = compare(prog, ref, cell.limits)
    log(f"[{cell.name}] reference {time.perf_counter() - t0:.3f} s; "
        f"losses program {prog['loss']} reference {ref['loss']}")
    correct = failed == 0 and all(
        c["limit"] is None or c["value"] <= c["limit"]
        for c in checks.values())
    line = {"correct": correct, "attempted": n, "failed": failed,
            "metrics": metrics, "device": device, **result,
            "checks": {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in checks.items()}}
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r}; worst leaf "
            f"{v.get('leaf', '-')})")
    print(json.dumps(_finite_or_none(line)), file=out, flush=True)
    return line


def traced_window(jax, cell: Cell, step, stack, params, pool, spans: Spans,
                  rec: Dict):
    """``TRACE_STEPS`` steps under the profiler (the split cell then
    traces as many plain reference steps on the same batches).  Returns
    (params, steps, losses, reduced trace)."""
    import shutil
    import tempfile
    from chipbench import trace as trace_mod
    ref_step = None
    if cell.mix.get("trace_reference_step"):
        from repro.core import hybrid_step as hs
        ref_step = hs.jitted_reference_step(stack, cell.mix["lr"])
        params, _ = ref_step(params, *pool[WARM_STEPS])   # compile first
        jax.block_until_ready(params)
    out = tempfile.mkdtemp(prefix="chipbench-trace-")
    spans.tracing = True
    losses = []
    jax.profiler.start_trace(out)
    try:
        before, marks = spans.snapshot(), []
        params, n, _, losses = drive(jax, step, params, pool, spans,
                                     WARM_STEPS + 1, steps=TRACE_STEPS,
                                     marks=marks)
        log_host(cell, marks, before, spans.snapshot())
        if ref_step is not None:
            for i in range(TRACE_STEPS):
                with spans("dispatch_reference"):
                    params, _ = ref_step(params, *pool[WARM_STEPS + 1 + i])
            jax.block_until_ready(params)
            rec["reference_steps_traced"] = TRACE_STEPS
    finally:
        jax.profiler.stop_trace()
        spans.tracing = False
    rec["steps_traced"] = n
    tr = trace_mod.load(trace_mod.find_xplane(out))
    shutil.rmtree(out, ignore_errors=True)
    return params, n, losses, tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = load_cell(a.workload)
    run(cell, a.seed, a.seconds, bool(a.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
