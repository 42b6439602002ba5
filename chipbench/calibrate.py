"""Readings for setting a cell's comparison limits, on the chip.

    python3 -m chipbench.calibrate --workload <cell> --seeds 1,2,3 \\
        --side program|f32|fp8|half_batch|no_exchange|drop_stream \\
        --out <dir>

``program``: the program's readings of the first steps, as a run takes
them in its set-up (``run.warm_steps``), one seed after another in one
process.  ``f32``: the plain reference's (``run.reference_readings``).
``fp8``: the control, the reference at the precision step below the
configuration's bf16.  The fault names put the reference with that
fault planted (``run.faults``) in the program's place.  Each seed's
readings go to ``<out>/<cell>.<side>.<seed>.json``; ``--compare`` then
prints, per seed, each number that a run compares for every side
against the f32 reference.  No measured window is needed for these.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

from chipbench import run as R


def _jsonable(r):
    return {k: (v.tolist() if hasattr(v, "tolist") else v)
            for k, v in r.items()}


def program_side(jax, cell, seeds, out):
    from chipbench import reference as ref
    spans = R.Spans()
    B, T = cell.mix["batch"], cell.mix["seq_len"]
    stack = R.program_stack(cell)
    p, step, mesh = R.build_step(jax, cell, stack, spans)
    where = R.placement(jax, mesh)
    for seed in seeds:
        t0 = time.perf_counter()
        params = ref.make_weights(cell.config, seed, where)
        pool = R.make_pool(jax, seed, cell.config["vocab_size"], B, T,
                           R.WARM_STEPS, where)
        params, r = R.warm_steps(jax, cell, step, params, seed, pool, spans)
        del params
        _write(out, cell, "program", seed, r)
        R.log(f"program seed {seed}: losses {r['loss']} "
              f"({time.perf_counter() - t0:.2f} s)")


def reference_side(jax, cell, seeds, out, side):
    B, T = cell.mix["batch"], cell.mix["seq_len"]
    where = R.placement(jax, None)
    mode = "fp8" if side == "fp8" else "f32"
    fault = None if side in ("f32", "fp8") else side
    for seed in seeds:
        t0 = time.perf_counter()
        pool = R.make_pool(jax, seed, cell.config["vocab_size"], B, T,
                           R.WARM_STEPS, where)
        r = R.reference_readings(jax, cell, seed, pool, mode=mode,
                                 fault=fault)
        _write(out, cell, side, seed, r)
        R.log(f"{side} seed {seed}: losses {r['loss']} "
              f"({time.perf_counter() - t0:.2f} s)")


def _write(out, cell, side, seed, r):
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{cell.name}.{side}.{seed}.json"),
              "w") as f:
        json.dump(_jsonable(r), f)


def compare_all(cell_name, out):
    """Per side and seed, the numbers a run compares, against the f32
    reference of the same seed; then per number the lower reading (the
    largest of the program's), the upper (the smallest control reading
    at three times the lower or more, or fault reading at ten times),
    and a limit between them with more room above the lower:
    ``lower**(1/3) * upper**(2/3)``."""
    rows = {}
    for path in sorted(glob.glob(os.path.join(out, f"{cell_name}.*.json"))):
        side, seed = os.path.basename(path)[:-5].split(".")[-2:]
        with open(path) as f:
            rows.setdefault(side, {})[seed] = json.load(f)
    refs = rows.get("f32", {})
    none = {"loss": None, "grad": None, "change": None}
    read = {}
    for side in sorted(rows):
        if side == "f32":
            continue
        for seed, r in sorted(rows[side].items()):
            if seed not in refs:
                continue
            c = R.compare(r, refs[seed], none)
            for k, v in c.items():
                read.setdefault(side, {}).setdefault(k, []).append(v["value"])
            print(f"{side:12s} seed {seed:>12s}  " + "  ".join(
                f"{k} {v['value']:.4e}" for k, v in c.items()))
    for k in none:
        lower = max(read["program"][k])
        uppers = sorted((min(d[k]), side) for side, d in read.items()
                        if side != "program" and min(d[k]) >=
                        (3 if side == "fp8" else 10) * lower)
        limit = lower ** (1 / 3) * uppers[0][0] ** (2 / 3) if uppers \
            else None
        print(f"{k}: lower {lower:.4e}  uppers {uppers}  limit "
              f"{limit if limit is None else f'{limit:.2g}'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--side", default="program")
    ap.add_argument("--out", required=True,
                    help="directory of the readings (written, or read back "
                         "with --compare)")
    ap.add_argument("--compare", action="store_true")
    a = ap.parse_args(argv)
    if a.compare:
        compare_all(a.workload, a.out)
        return 0
    cell = R.load_cell(a.workload)
    # The reference runs on one chip whatever the cell's mesh.
    jax = R.bootstrap(cell.chips if a.side == "program" else 1)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    if a.side == "program":
        program_side(jax, cell, seeds, a.out)
    else:
        reference_side(jax, cell, seeds, a.out, a.side)
    return 0


if __name__ == "__main__":
    sys.exit(main())
