"""The comparison that decides ``correct`` catches what it must: a run
with the timed path broken underneath comes out not correct, and so does
the control, the reference at the precision step below the one the
configuration states put in the program's place.  Tiny cells on the
CPU, float32 programs, so the limits can be tight."""
import io

import jax
import pytest

from chipbench import run
from chipbench.tests import tiny


@pytest.fixture(scope="module", autouse=True)
def keep_compiled_programs():
    """A run frees every compiled program before its reference runs, to
    give the chip's memory back; on the CPU that only makes each of this
    module's runs compile everything again."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "clear_caches", lambda: None)
        yield


def _broken_run(monkeypatch, mix, break_step):
    build = run.build_step

    def broken_build(jax_, cell, stack, spans):
        p, step, mesh = build(jax_, cell, stack, spans)
        return p, break_step(p, step, cell), mesh
    monkeypatch.setattr(run, "build_step", broken_build)
    return run.run(tiny.cell(mix=mix), 11, 0.1, False, require_tpu=False,
                   out=io.StringIO())


def _unchanged(p, step, cell):
    def f(params, x, y):
        _, loss = step(jax.tree.map(lambda a: a + 0, params), x, y)
        return params, loss
    return f


def _half_batch(p, step, cell):
    from repro.api import plan
    B = cell.mix["batch"]
    half = plan(p.model, run.make_fleet(cell.mix["fleet"]), B // 2)
    hstep = half.step_fn(lr=cell.mix["lr"])

    def f(params, x, y):
        return hstep(params, x[:B // 2], y[:B // 2])
    return f


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("mix", [tiny.PLANNED, tiny.SPLIT],
                         ids=["planned", "split"])
def test_broken_step_is_not_correct(monkeypatch, mix, fault):
    line = _broken_run(monkeypatch, mix, fault)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("side", ["fp8", "drop_stream", "no_exchange"])
def test_control_and_planted_faults_are_not_correct(side):
    """The reference put in the program's place: at the precision below
    the configuration's, or with the device stream's front gradient or
    the data-parallel exchange left out."""
    jax_ = run.bootstrap(1, require_tpu=False)
    cell = tiny.cell(mix=dict(tiny.SPLIT, cloud_mesh={"axis": "data",
                                                       "chips": 2}))
    pool = run.make_pool(jax_, 3, cell.config["vocab_size"],
                         cell.mix["batch"], cell.mix["seq_len"], 3,
                         run.placement(jax_, None))
    ref = run.reference_readings(jax_, cell, 3, pool)
    bad = run.reference_readings(
        jax_, cell, 3, pool, mode="fp8" if side == "fp8" else "f32",
        fault=None if side == "fp8" else side)
    checks = run.compare(bad, ref, cell.limits)
    assert any(c["value"] > c["limit"] for c in checks.values())


def test_calibration_sets_limits_between_the_readings(tmp_path, capsys):
    """``calibrate --compare``: lower = the program's largest reading,
    upper = the smallest control reading at 3x or fault reading at 10x."""
    import json

    from chipbench import calibrate
    ref = {"loss": [100.0], "grad1": [1.0, 1.0, 1.0],
           "grad_exact": [1.0, 1.0, 1.0], "change": [1.0, 1.0, 1.0]}

    def reading(gap):
        return {"loss": [100.0 * (1 + gap)], "grad1": [1 + gap, 1, 1],
                "change": [1 + gap, 1, 1]}
    sides = {"f32": ref, "program": reading(1e-3), "fp8": reading(2e-3),
             "half_batch": reading(0.5)}
    for side, r in sides.items():
        (tmp_path / f"c.{side}.7.json").write_text(json.dumps(r))
    calibrate.compare_all("c", str(tmp_path))
    out = capsys.readouterr().out
    # fp8 at 2x the program is no upper; the fault at 500x is.
    assert "uppers [(0.5, 'half_batch')]" in out
    assert "limit 0.063" in out           # 1e-3 ** (1/3) * 0.5 ** (2/3)
