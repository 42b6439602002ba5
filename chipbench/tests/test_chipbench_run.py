"""The harness: every cell, and every layer kind of every configuration,
resolves to its files by name; a run refuses the CPU and a checkout
without the program; a run of a tiny cell on the CPU (the chip check
skipped) is correct and prints the result line."""
import glob
import io
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench import layers, metrics, run
from chipbench.tests import tiny


@pytest.fixture(scope="module", autouse=True)
def keep_compiled_programs():
    """A run frees every compiled program before its reference runs, to
    give the chip's memory back; on the CPU that only makes each of this
    module's runs compile everything again."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "clear_caches", lambda: None)
        yield

ROOT = run.ROOT


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_to_its_files():
    bench = _bench()
    assert bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        cfg = run.load_json(ROOT, c["file"])
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced_from"], key
            assert cfg[key] != cfg["reduced_from"][key], key
    for path in glob.glob(os.path.join(ROOT, "chipbench", "configs",
                                       "*.json")):
        for kind in set(run.load_json(path)["layers"]) - set(layers.ENDS):
            mod = layers.load(kind)
            assert all(callable(getattr(mod, f, None))
                       for f in ("shapes", "forward", "flops")), kind
    names = {m["name"] for m in bench["per_layer"]}
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.mix["seq_len"] > 0 and set(cell.limits) == {
            "loss", "grad", "change"}
        assert {m["name"] for m in cell.per_layer} <= names
        for m in cell.per_layer:
            assert callable(metrics.load(m["name"]).read)
    for m in bench["per_layer"]:
        assert set(m.get("workloads", [])) <= {
            w["name"] for w in bench["workloads"]}
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         _bench()["workloads"][0]["name"], "--seed", "5000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_the_cpu():
    r = _cli(ROOT)
    assert r.returncode != 0 and "needs a TPU" in r.stderr
    assert "{" not in r.stdout


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0 and "no program" in r.stderr
    assert "{" not in r.stdout


@pytest.mark.parametrize("mix", [tiny.PLANNED, tiny.SPLIT],
                         ids=["planned", "split"])
def test_tiny_cell_is_correct(mix):
    out = io.StringIO()
    line = run.run(tiny.cell(mix=mix), 2 ** 31 + 7, 0.2, False,
                   require_tpu=False, out=out)
    assert json.loads(out.getvalue().splitlines()[-1]) == line
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
