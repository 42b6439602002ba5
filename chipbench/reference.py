"""Plain reference of the language models the benchmark trains.

Straightforward ``jax.numpy`` at float32 with ``highest`` matmul
precision: no kernels, no cut points, no batch split.  It imports nothing
of the program under test and takes nothing the program made; it reads
the weights the benchmark made from the seed (``make_weights``), laid out
as the program's parameter list (one dict per cut point), and follows
the layer equations of the published models (``configs/<name>.json``
lists every departure of the run from them).  This module holds the
chain's two ends, the embedding and the head with its loss; each kind
of layer between them is a module of its own, found by its name
(``chipbench/layers/<kind>.py``).

A training step here is plain synchronous SGD on the mean over the batch
of each sequence's summed token cross-entropy, as the program's step is:
``W <- W - lr * grad``, with each weight stored back in the dtype the
configuration states, as the program stores it.  Gradients are float32
throughout.

Memory: the step runs layer by layer and one sequence at a time (the
boundary activations of every sequence are kept, the layer's own
activations are recomputed in its backward), so the float32 reference
fits the chip beside the bf16 weights.

``mode="fp8"`` is the control, the precision step below the bf16 the
configuration states: every matmul takes its operands in float8 e4m3
and its backward the cotangent in e5m2, each with a per-tensor scale,
as fp8 training does; everything else stays float32.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import layers

F32 = jnp.float32
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def _q(x: jax.Array, dtype) -> jax.Array:
    """``x`` rounded to the float8 ``dtype`` with a per-tensor scale."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec: str, a, b):
    return jnp.einsum(spec, _q(a, E4M3), _q(b, E4M3), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    qa, qb = _q(a, E4M3), _q(b, E4M3)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     *res)
    return vjp(_q(g, E5M2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(mode: str, spec: str, a, b):
    """A matmul: float32 at ``highest`` precision, or, for the control,
    with its operands in float8 e4m3 and the cotangent of its backward
    in e5m2, each with a per-tensor scale."""
    if mode == "fp8":
        return _fp8_einsum(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    """Rotate-half RoPE over positions 0..T-1.  x: [T, H, hd]."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------------
# the chain's ends; the layers between are ``chipbench/layers/<kind>.py``
# ---------------------------------------------------------------------------

def head_loss(c: Dict, mode: str, p, h, y):
    hn = rms_norm(h, p["final_norm"]["w"], c["rms_norm_eps"])
    logits = _mm(mode, "td,dv->tv", hn, p["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _key(seed: int) -> jax.Array:
    """Seeds may exceed 32 bits: fold the high part into the key."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def layer_shapes(c: Dict) -> List[Dict]:
    """Shapes and dtypes of every leaf, as a list of per-layer dicts of
    ``(shape, dtype, init)``."""
    D, V, bf = c["hidden_size"], c["vocab_size"], c["dtype"]
    out: List[Dict] = []
    for kind in c["layers"]:
        if kind == "embed":
            out.append({"embed": ((V, D), bf, "matrix")})
        elif kind == "head":
            out.append({"final_norm": {"w": ((D,), bf, "zeros")},
                        "lm_head": ((D, V), bf, "matrix")})
        else:
            out.append(layers.load(kind).shapes(c))
    return out


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def _init_leaf(key, spec, inits: Dict):
    shape, dtype, init = spec
    if init == "matrix":       # truncated normal, std 1/sqrt(fan_in)
        v = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) \
            / np.sqrt(shape[0])
    elif init == "zeros":      # norms carry (1 + w)
        v = jnp.zeros(shape, F32)
    else:                      # the layer kind's own
        v = inits[init](key, shape)
    return v.astype(dtype)


def init_layer(c: Dict, key: jax.Array, i: int):
    """Layer ``i``'s weights; each leaf's key is folded from its index
    among all leaves, so a layer can be made again alone."""
    specs = layer_shapes(c)
    before = sum(len(jax.tree.leaves(s, is_leaf=_is_spec))
                 for s in specs[:i])
    inits = layers.declared(c["layers"][i], "INIT", {})
    leaves, tree = jax.tree.flatten(specs[i], is_leaf=_is_spec)
    return jax.tree.unflatten(tree, [
        _init_leaf(jax.random.fold_in(key, before + j), s, inits)
        for j, s in enumerate(leaves)])


@functools.lru_cache(maxsize=None)
def _init_program(ckey: str, i: int):
    c = _CONFIGS[ckey]
    return jax.jit(lambda key: init_layer(c, key, i))


def make_weights(c: Dict, seed: int, sharding=None) -> List:
    """The weights of seed ``seed``, made on the device by one jitted
    program per cut point, in the dtypes the configuration states.  The
    same programs make them again bit for bit (``change_norms``), which
    one program over every layer would not: XLA fuses and rounds its
    float32 work differently in each program.  ``sharding``: where to
    put them (by a copy; default: the first chip)."""
    ck, key = _config_key(c), _key(seed)
    w = [_init_program(ck, i)(key) for i in range(len(c["layers"]))]
    return w if sharding is None else jax.device_put(w, sharding)


# ---------------------------------------------------------------------------
# the training step, layer by layer
# ---------------------------------------------------------------------------

def _up(p):
    return jax.tree.map(lambda a: a.astype(F32), p)


def _as_stored(x, dtype):
    """``x`` (float32) rounded to ``dtype`` and back, in a form XLA keeps:
    it may drop a convert to bf16 and back as excess precision, but not
    a ``reduce_precision``."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


@functools.lru_cache(maxsize=None)
def _programs(ckey: str, mode: str):
    c = _CONFIGS[ckey]

    @jax.jit
    def embed(p, x):
        return p["embed"][x].astype(F32)

    @functools.partial(jax.jit, static_argnums=0)
    def fwd(kind, p, h):
        return layers.load(kind).forward(c, mode, _up(p), h)

    @functools.partial(jax.jit, static_argnums=0)
    def bwd(kind, p, h, g):
        _, vjp = jax.vjp(
            lambda pp, hh: layers.load(kind).forward(c, mode, pp, hh),
            _up(p), h)
        return vjp(g)

    @jax.jit
    def head(p, h, y):
        loss, (gp, gh) = jax.value_and_grad(
            lambda pp, hh: head_loss(c, mode, pp, hh, y),
            argnums=(0, 1))(_up(p), h)
        return loss, gp, gh

    @jax.jit
    def embed_grad(p, x, g):
        return {"embed": jnp.zeros(p["embed"].shape, F32).at[x].add(g)}

    return embed, fwd, bwd, head, embed_grad


_CONFIGS: Dict[str, Dict] = {}


def _config_key(c: Dict) -> str:
    import json
    k = json.dumps(c, sort_keys=True)
    _CONFIGS[k] = c
    return k


@functools.partial(jax.jit, donate_argnums=0)
def _sgd(p, g, lr):
    """SGD on one layer, stored back in each weight's dtype; also the
    per-leaf norms of the gradient and of the change applied."""
    new32 = jax.tree.map(lambda w, gg: _as_stored(w.astype(F32) - lr * gg,
                                                   w.dtype), p, g)
    gn = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(gg)))
                    for gg in jax.tree.leaves(g)])
    dn = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a - b.astype(F32))))
                    for a, b in zip(jax.tree.leaves(new32),
                                    jax.tree.leaves(p))])
    new = jax.tree.map(lambda a, w: a.astype(w.dtype), new32, p)
    return new, gn, dn


def sgd_step(c: Dict, params: List, x, y, lr: float, mode: str = "f32",
             grad_rows: Optional[Sequence[Sequence[int]]] = None,
             loss_rows: Optional[Sequence[int]] = None,
             divisor: Optional[float] = None):
    """One SGD step on the batch ``(x, y)`` ([B, T] int32 each).

    ``grad_rows[i]`` lists the sequences whose gradient reaches layer
    ``i`` (default: all), ``loss_rows`` those whose loss counts, and
    ``divisor`` the count the summed gradient is divided by (default B):
    the faults the comparison must catch are written with these.
    Returns ``(new_params, loss, grad_norms, step_norms)``: the loss
    (summed over ``loss_rows``, over ``divisor``), and per leaf the norm
    of the gradient the update used and of the change it made to the
    stored weight.  ``params`` is consumed."""
    embed, fwd, bwd, head, embed_grad = _programs(_config_key(c), mode)
    kinds = c["layers"]
    n, B = len(kinds), x.shape[0]
    rows = list(range(B))
    grad_rows = grad_rows or [rows] * n
    loss_rows = rows if loss_rows is None else list(loss_rows)
    div = float(B if divisor is None else divisor)
    acts = []                     # acts[r][i]: input of layer i + 1
    for r in rows:
        h = embed(params[0], x[r])
        a = [h]
        for i in range(1, n - 1):
            h = fwd(kinds[i], params[i], h)
            a.append(h)
        acts.append(a)
    new = list(params)
    loss = 0.0
    cot = {}
    g_acc = None
    for r in rows:
        lr_, gp, gh = head(params[n - 1], acts[r][n - 2], y[r])
        if r in loss_rows:
            loss = loss + lr_
        cot[r] = gh
        if r in grad_rows[n - 1]:
            g_acc = gp if g_acc is None else jax.tree.map(jnp.add, g_acc, gp)
    gnorms, dnorms = [None] * n, [None] * n

    def update(i, g):
        g = jax.tree.map(lambda t: t / div, g)
        new[i], gnorms[i], dnorms[i] = _sgd(params[i], g, lr)

    update(n - 1, g_acc)
    for i in range(n - 2, 0, -1):
        g_acc = None
        for r in rows:
            gp, cot[r] = bwd(kinds[i], params[i], acts[r][i - 1], cot[r])
            if r in grad_rows[i]:
                g_acc = gp if g_acc is None else \
                    jax.tree.map(jnp.add, g_acc, gp)
        for r in rows:
            acts[r][i - 1] = None
        update(i, g_acc)
    g_acc = None
    for r in rows:
        if r in grad_rows[0]:
            ge = embed_grad(params[0], x[r], cot[r])
            g_acc = ge if g_acc is None else jax.tree.map(jnp.add, g_acc, ge)
    update(0, g_acc)
    flat = lambda vs: np.concatenate([np.asarray(v) for v in vs])
    return new, float(loss) / div, flat(gnorms), flat(dnorms)


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32) -
                                                  y.astype(F32))))
                      for x, y in zip(jax.tree.leaves(a),
                                      jax.tree.leaves(b))])


def change_norms(c: Dict, params: List, seed: int) -> np.ndarray:
    """Per-leaf float32 norms of ``params`` minus the seed's weights,
    each layer made again by its own program and copied to where
    ``params`` lives (so one extra layer is held at a time)."""
    ck, key = _config_key(c), _key(seed)
    out = []
    for i, p in enumerate(params):
        w = jax.device_put(_init_program(ck, i)(key),
                           jax.tree.leaves(p)[0].sharding)
        out.append(np.asarray(_diff_norms(p, w)))
    return np.concatenate(out)
