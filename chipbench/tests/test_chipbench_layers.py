"""Layer kinds as files (``chipbench/layers/``): the weights the reference
makes are pinned bit for bit, and a kind that is only a new file on the
package's search path gives what the kind it copies gives: the same
weights, reference step, FLOPs and attention-backward need; and a kind
can make a leaf by an init of its own."""
import hashlib
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops as F
from chipbench import layers, metrics
from chipbench import reference as R
from chipbench.layers import attn
from chipbench.tests import tiny

SEED = 2 ** 31 + 7
# sha256 of the float32 bytes of every leaf of make_weights(tiny.DENSE,
# SEED), in tree order, on the CPU.
DENSE_WEIGHTS = \
    "5076f07dc193744355c0e6ce5bebc3ccc2c80781a9f719c1fa4ca10557aa9810"


def _digest(weights) -> str:
    h = hashlib.sha256()
    for a in jax.tree.leaves(weights):
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_weights_are_pinned():
    assert _digest(R.make_weights(tiny.DENSE, SEED)) == DENSE_WEIGHTS


@pytest.fixture
def attn_copy(tmp_path, monkeypatch):
    """``attn.py`` copied, under the kind name ``attn_copy``, into a
    directory put on the package's search path."""
    name = f"{layers.__name__}.attn_copy"
    shutil.copy(attn.__file__, tmp_path / "attn_copy.py")
    monkeypatch.setattr(layers, "__path__", [*layers.__path__,
                                             str(tmp_path)])
    yield layers.load("attn_copy")
    sys.modules.pop(name, None)


def test_a_kind_is_added_as_a_file(attn_copy, tmp_path):
    assert attn_copy.__file__ == str(tmp_path / "attn_copy.py")
    copy = dict(tiny.DENSE, layers=[
        k if k in layers.ENDS else "attn_copy"
        for k in tiny.DENSE["layers"]])
    w, wc = R.make_weights(tiny.DENSE, SEED), R.make_weights(copy, SEED)
    assert jax.tree.structure(w) == jax.tree.structure(wc)
    assert _digest(wc) == DENSE_WEIGHTS

    x, y = jax.random.randint(jax.random.PRNGKey(3), (2, 2, 32), 0,
                              tiny.DENSE["vocab_size"])
    new, loss, gn, dn = R.sgd_step(tiny.DENSE, w, x, y, 1e-2)
    new_c, loss_c, gn_c, dn_c = R.sgd_step(copy, wc, x, y, 1e-2)
    assert loss_c == loss
    np.testing.assert_array_equal(gn_c, gn)
    np.testing.assert_array_equal(dn_c, dn)
    assert _digest(new_c) == _digest(new)

    assert F.step_flops(copy, 32, 2) == F.step_flops(tiny.DENSE, 32, 2)
    step_cost = metrics.load("flash_attention_bwd_roofline").step_cost
    assert step_cost(copy, 32, 2, 4) == step_cost(tiny.DENSE, 32, 2, 4)


def test_a_kind_brings_its_own_init(attn_copy, monkeypatch):
    """A leaf whose init the kind names (``INIT``) is made by it; every
    other leaf keeps its key and its bits."""
    shapes = attn_copy.shapes

    def marked(c):
        s = shapes(c)
        s["ln1"]["w"] = s["ln1"]["w"][:2] + ("minus_two",)
        return s
    monkeypatch.setattr(attn_copy, "shapes", marked)
    monkeypatch.setattr(attn_copy, "INIT", {
        "minus_two": lambda key, shape: jnp.full(shape, -2.0)},
        raising=False)
    copy = dict(tiny.DENSE, layers=[
        k if k in layers.ENDS else "attn_copy"
        for k in tiny.DENSE["layers"]], why="own init")
    w, wc = R.make_weights(tiny.DENSE, SEED), R.make_weights(copy, SEED)
    for i in (1, 2):
        assert (np.asarray(wc[i]["ln1"]["w"]) == -2.0).all()
        wc[i]["ln1"]["w"] = w[i]["ln1"]["w"]
    assert _digest(wc) == DENSE_WEIGHTS
