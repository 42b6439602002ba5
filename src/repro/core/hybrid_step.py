"""Hybrid-parallelism execution engine (§IV-B) with exact SGD semantics.

Executes one HierTrain iteration the way the paper describes it — workers
holding *separate copies* of their assigned layers, activations crossing at
the cut points, and only frontend gradients being exchanged — and produces
the *same* update as vanilla SGD over the full batch ``B`` (sample-weighted
gradient averaging; see DESIGN.md §3 for why weighting is required for
exactness).  One entry point, :func:`hybrid_sgd_step`, serves every
fleet (DESIGN.md §3): M TASK-S streams with per-stream cuts ``m_s[i]``
under E edge servers (``stream_edge``), one TASK L, one TASK O.  The
star is the tree with every stream on edge 0, and the paper's
three-worker triple is the star at ``M = 1``
(``MultiSchedule.from_schedule``).

The step is model-agnostic (DESIGN.md §8): it takes anything
:func:`repro.core.layerstack.as_layerstack` accepts — a bare
:class:`repro.models.cnn.LayeredModel` (traced bit-identically to the
pre-adapter code) or an adapter such as the LM model-zoo stack.  The stack
contract is what makes the routing generic: ``params`` is a list with one
pytree per cut-point, ``apply_segment`` runs a contiguous cut range, and
``sum_loss`` is the per-sample-*sum* objective (so one division by ``B``
yields exact batch-B SGD).

The forward routing (Fig. 4, one TASK-S stream per non-o, non-l worker):

* stream ``i``: layers ``1..m_s[i]`` on its ``b_s[i]`` samples -> ships
  ``h_i``; streams sharing ``(cut, edge)`` are concatenated on the edge.
* ``worker_l``: layers ``1..m_l`` on its ``b_l`` samples -> ships ``h_l``.
* ``worker_o``: walks layers ``1..m_l`` on its own samples, merging each
  arriving block at its cut in ascending-cut order, then takes ``h_l``
  at ``m_l`` and runs layers ``m_l+1..N`` on everything.

The backward pass retraces this routing (handled by AD through the composed
function — gradients w.r.t. stream ``i``'s copy are exactly what its worker
computes after receiving the intermediate result at layer ``m_s[i]+1``).
Weight update: per-layer gradient exchange over the *shared* frontend only.

Every phase runs under a named scope (``repro.obs.scope``), so each
operation of the compiled step names the phase it belongs to:
``hier.stream{i}`` (TASK-S stream ``i``'s front segment),
``hier.stream_l`` (TASK L's), ``hier.cloud`` (worker_o's walk),
``hier.merge`` / ``hier.edge_merge`` (the concatenations at cuts),
``hier.exchange`` (the sums over each front layer's copies),
``hier.update`` (SGD) and ``hier.tail_psum`` (the sharded tail's psum);
the plain step runs under ``reference``.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.cost_model import MultiSchedule, Schedule
from repro.core.layerstack import as_layerstack
from repro.core.wire import wire_act_bytes, wire_codec, wire_grad_bytes

Params = List[Any]


def reference_sgd_step(model, params: Params, x: jax.Array,
                       y: jax.Array, lr: float) -> Tuple[Params, jax.Array]:
    """Vanilla full-batch SGD step: the ground truth the hybrid step must
    reproduce."""
    stack = as_layerstack(model)
    N = stack.num_layers

    def loss_fn(p):
        return stack.sum_loss(stack.apply_segment(p, x, 0, N), y) / \
            x.shape[0]
    with obs.scope("reference"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return new, loss


def split_batch(x: jax.Array, y: jax.Array, sched: MultiSchedule
                ) -> Dict[str, object]:
    """Assign the first ``b_o`` samples to o, the next ``b_s[i]`` to each
    TASK-S stream in ``s_workers`` order, and the remainder to l."""
    bo, bl = sched.b_o, sched.b_l
    assert bo + sum(sched.b_s) + bl == x.shape[0]
    out: Dict[str, object] = {"o": (x[:bo], y[:bo])}
    streams = []
    at = bo
    for bi in sched.b_s:
        streams.append((x[at:at + bi], y[at:at + bi]))
        at += bi
    out["s"] = tuple(streams)
    out["l"] = (x[at:], y[at:])
    return out


def hybrid_sgd_step(model, params: Params, batches: Dict[str, object],
                    m_s: Sequence[int], m_l: int, lr: float,
                    wire: str = "none",
                    stream_edge: Sequence[int] | None = None,
                    cloud_mesh=None) -> Tuple[Params, jax.Array]:
    """One HierTrain iteration.  Returns (updated params, mean loss).

    ``params`` plays the role of the consensus weights each worker starts
    the iteration with (they are equal after every weight-update phase).
    ``batches`` is :func:`split_batch`'s output: worker_o's samples, one
    ``(x, y)`` per TASK-S stream, and TASK L's.

    ``stream_edge[i]`` names the edge hosting TASK-S stream ``i`` (device
    streams sit under their radio's edge; an edge's own stream under
    itself); ``None`` puts every stream on edge 0, which is the star and,
    at ``M = 1``, the paper's triple.  Streams sharing ``(cut, edge)`` are
    concatenated *on the edge* into one activation block before joining
    worker_o's ascending-cut walk.  Concatenation is arithmetic-free, so
    the sample order, every matmul batch and the loss-sum reduction
    order are the same whichever edges the streams sit under.

    ``wire`` selects the cut-point transfer codec (``repro.core.wire``):
    ``"int8"`` quantizes the shipped activations forward and — via the
    codec's custom VJP — the returning activation-gradients backward;
    ``"none"`` applies no codec.  A cut at 0 is a raw-input upload (the
    ``sample_bytes`` channel), so the codec only touches crossings with
    ``m > 0``.

    ``cloud_mesh`` (optional) runs the cloud-resident tail segment
    ``m_l..N`` data-parallel over the mesh's dp axes via ``shard_map``
    (:func:`_sharded_tail_grads`); the default ``None`` keeps the single
    ``value_and_grad`` program.
    """
    stack = as_layerstack(model)
    N = stack.num_layers
    codec = wire_codec(wire)
    m_s = tuple(int(m) for m in m_s)
    M = len(m_s)
    eo = tuple(int(e) for e in stream_edge) if stream_edge is not None \
        else (0,) * M
    assert len(eo) == M
    x_o, y_o = batches["o"]
    s_streams = batches["s"]
    x_l, y_l = batches["l"]
    assert len(s_streams) == M
    assert all(0 <= m <= m_l for m in m_s) and m_l <= N
    b_s = [sx.shape[0] for sx, _ in s_streams]
    b_o, b_l = x_o.shape[0], x_l.shape[0]
    B = b_o + sum(b_s) + b_l
    # Ascending-cut order with the hosting edge (then stream index)
    # breaking ties; maximal runs of equal (cut, edge) are one edge-side
    # merge each.
    join_order = sorted((i for i in range(M) if b_s[i]),
                        key=lambda i: (m_s[i], eo[i], i))
    groups: List[Tuple[int, List[int]]] = []
    for i in join_order:
        if groups and groups[-1][0] == m_s[i] and eo[groups[-1][1][-1]] == \
                eo[i]:
            groups[-1][1].append(i)
        else:
            groups.append((m_s[i], [i]))

    # Worker-local copies: stream i holds layers 1..m_s[i], TASK L 1..m_l,
    # worker_o all of them.
    p_o = params
    p_s = [params[:m] for m in m_s]
    p_l = params[:m_l]

    def front(p_o: Params, p_s: List[Params], p_l: Params) -> jax.Array:
        """Everything up to the cloud boundary ``m_l``: per-stream
        frontends, per-edge merges, worker_o's walk, TASK L's arrival."""
        h = []
        for i in range(M):
            with obs.scope(f"hier.stream{i}"):
                a = stack.apply_segment(p_s[i], s_streams[i][0], 0, m_s[i]) \
                    if b_s[i] else None
                if codec is not None and a is not None and m_s[i] > 0:
                    a = codec(a)
            h.append(a)
        with obs.scope("hier.stream_l"):
            h_l = stack.apply_segment(p_l, x_l, 0, m_l) if b_l else None
            if codec is not None and h_l is not None and m_l > 0:
                h_l = codec(h_l)
        cur = x_o
        prev = 0
        for cut, members in groups:
            if cut != prev:
                with obs.scope("hier.cloud"):
                    cur = stack.apply_segment(p_o, cur, prev, cut)
                prev = cut
            with obs.scope("hier.edge_merge"):
                blk = h[members[0]] if len(members) == 1 else \
                    jnp.concatenate([h[i] for i in members], axis=0)
            with obs.scope("hier.merge"):
                cur = jnp.concatenate([cur, blk], axis=0)
        with obs.scope("hier.cloud"):
            cur = stack.apply_segment(p_o, cur, prev, m_l)
        with obs.scope("hier.merge"):
            if h_l is not None:
                cur = jnp.concatenate([cur, h_l], axis=0)
        return cur

    with obs.scope("hier.merge"):
        labels = jnp.concatenate(
            [y_o] + [s_streams[i][1] for i in join_order] + [y_l], axis=0)

    if cloud_mesh is None:
        def iteration_loss(p_o: Params, p_s: List[Params], p_l: Params
                           ) -> jax.Array:
            cur = front(p_o, p_s, p_l)
            with obs.scope("hier.cloud"):
                logits = stack.apply_segment(p_o, cur, m_l, N)
            return stack.sum_loss(logits, labels)

        total_loss, (g_o, g_s, g_l) = jax.value_and_grad(
            iteration_loss, argnums=(0, 1, 2))(p_o, p_s, p_l)
    else:
        total_loss, g_o, g_s, g_l = _sharded_tail_grads(
            stack, front, labels, p_o, p_s, p_l, m_l, N, B, cloud_mesh)

    # --- weight-update phase: layer-wise gradient exchange ---------------
    return _exchange_update(params, g_o, g_s, g_l, m_s, b_s, m_l, b_l, lr,
                            B), total_loss / B


def hybrid_step_from_schedule(model, params: Params, x: jax.Array,
                              y: jax.Array, sched: MultiSchedule, lr: float,
                              wire: str = "none",
                              stream_edge: Sequence[int] | None = None,
                              cloud_mesh=None) -> Tuple[Params, jax.Array]:
    return hybrid_sgd_step(model, params, split_batch(x, y, sched),
                           sched.m_s, sched.m_l, lr, wire=wire,
                           stream_edge=stream_edge, cloud_mesh=cloud_mesh)


def _exchange_update(params: Params, g_o, g_s, g_l, m_s, b_s, m_l: int,
                     b_l: int, lr: float, B: int) -> Params:
    """Sum each front layer's per-sample-sum gradients over its copies,
    then one SGD update scaled by ``1/B`` (exact batch-``B`` SGD)."""
    new_params: Params = []
    for i in range(len(params)):
        g = g_o[i]
        with obs.scope("hier.exchange"):
            for d in range(len(m_s)):
                if i < m_s[d] and b_s[d]:
                    g = jax.tree.map(jnp.add, g, g_s[d][i])
            if i < m_l and b_l:
                g = jax.tree.map(jnp.add, g, g_l[i])
        with obs.scope("hier.update"):
            new_params.append(jax.tree.map(
                lambda p, gg: p - lr * (gg / B), params[i], g))
    return new_params


def _sharded_tail_grads(stack, front, labels, p_o: Params,
                        p_s: List[Params], p_l: Params, m_l: int, N: int,
                        B: int, mesh):
    """Loss + grads with the cloud tail ``m_l..N`` data-parallel under
    ``shard_map``.  One mapped body per dp shard: the front runs
    replicated on every shard (its Pallas kernels cannot be
    auto-partitioned, so it must sit inside the manual region too), the
    shard takes its slice of the arrived batch through the tail's
    ``value_and_grad``, and ``jax.vjp`` carries that slice's activation
    cotangent back through the front.  Loss and every parameter grad
    are ``psum``-reduced over the dp axes, which adds the slices'
    contributions exactly once."""
    from jax.sharding import PartitionSpec as P

    from repro.distrib import sharding

    dp = sharding.dp_axes(mesh)
    if not dp:
        raise ValueError("cloud_mesh has no data-parallel axes "
                         "('pod'/'data'); got axes "
                         f"{tuple(mesh.axis_names)}")
    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]
    if B % n_shards != 0:
        raise ValueError(
            f"global batch {B} is not divisible by the cloud mesh's "
            f"{n_shards} data-parallel shards; pick a schedule whose "
            "batch split is a multiple of the dp size")
    n_local = B // n_shards

    def tail_loss(p_o: Params, cur: jax.Array, lab: jax.Array) -> jax.Array:
        with obs.scope("hier.cloud"):
            logits = stack.apply_segment(p_o, cur, m_l, N)
        return stack.sum_loss(logits, lab)

    def body(p_o: Params, p_s: List[Params], p_l: Params,
             lab_l: jax.Array):
        cur, front_vjp = jax.vjp(front, p_o, p_s, p_l)
        start = jax.lax.axis_index(dp) * n_local
        cur_l = jax.lax.dynamic_slice_in_dim(cur, start, n_local)
        loss_l, (gp_tail, gc_l) = jax.value_and_grad(
            tail_loss, argnums=(0, 1))(p_o, cur_l, lab_l)
        g_cur = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros_like(cur), gc_l, start, 0)
        g_o, g_s, g_l = front_vjp(g_cur)
        g_o = jax.tree.map(jnp.add, g_o, gp_tail)
        with obs.scope("hier.tail_psum"):
            return jax.lax.psum((loss_l, g_o, g_s, g_l), dp)

    spec_lab = P(dp, *([None] * (labels.ndim - 1)))
    sharded = jax.shard_map(
        body, in_specs=(P(), P(), P(), spec_lab), out_specs=P(),
        axis_names=set(dp), check_vma=False, mesh=mesh)
    # jit: jax's eager shard_map path rejects a partially manual mesh
    # (it re-checks the specs against every mesh axis); traced, it is fine.
    # repro-lint: disable-next=RA102 inlined into the caller's jitted step; only eager calls rebuild it
    return jax.jit(sharded)(p_o, p_s, p_l, labels)


def tree_stream_edges(profile, net, sched: MultiSchedule) -> Tuple[int, ...]:
    """Per-TASK-S-stream hosting edge for a tree schedule: a device
    stream sits under its radio's edge, an edge's own stream under
    itself, and a cloud-hosted stream merges with the front group
    (index 0).  On an E=1 tree every stream maps to edge 0, which is
    what keeps the traced step identical to the star's."""
    D = profile.num_devices
    E = net.num_edges
    eo = net.edge_of
    out = []
    for w in sched.s_workers:
        i = profile.widx[w]
        if i < D:
            out.append(eo[i])
        else:
            j = i - D
            out.append(j if j < E else 0)
    return tuple(out)


# ---------------------------------------------------------------------------
# Compiled fast path.  The cuts and learning rate are static (they select
# the program structure), the params are donated (the step consumes the old
# consensus weights and returns the new ones), and compiled steps live in a
# *bounded LRU*: with the LM config zoo reachable through the LayerStack
# adapter, the seed's grow-forever dict (which pinned every model through
# the compiled closures) would leak models and executables across a long
# session.  Keys use an id-based weak model handle; the cache entry pins
# the model only while cached — the id can therefore never be recycled
# while its entry is live, and eviction (or :func:`clear_jit_cache`)
# releases both the executable and the model.
# ---------------------------------------------------------------------------

JIT_CACHE_SIZE = 32


class _JitStepCache:
    """Bounded LRU of compiled step functions.

    ``key`` is ``(kind, id(model), *static_args)``.  The value stores the
    compiled function *and* the model it closed over: the pin is what makes
    the id-keyed handle sound (a live key's id cannot be reused by a new
    model), and dropping the entry releases the model for GC — the seed
    cache held every model forever.
    """

    def __init__(self, maxsize: int = JIT_CACHE_SIZE) -> None:
        assert maxsize >= 1
        self.maxsize = maxsize
        self._entries: "OrderedDict[Tuple, Tuple[Callable, Any]]" = \
            OrderedDict()

    def get(self, key: Tuple) -> Callable | None:
        entry = self._entries.get(key)
        if entry is None:
            return None
        self._entries.move_to_end(key)
        return entry[0]

    def put(self, key: Tuple, fn: Callable, model: Any) -> None:
        self._entries[key] = (fn, model)
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._entries

    def clear(self) -> None:
        self._entries.clear()


_JIT_CACHE = _JitStepCache()


def clear_jit_cache() -> None:
    """Drop every cached compiled step (releases the pinned models)."""
    _JIT_CACHE.clear()


def _cached_step(key: Tuple, model, make: Callable[[], Callable]
                 ) -> Callable:
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = make()
        _JIT_CACHE.put(key, fn, model)
    return fn


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under a stable program name: the compiled module, its
    runs in a profiler trace and its compile events carry it."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def jitted_hybrid_step(model, m_s: Sequence[int], m_l: int, lr: float,
                       wire: str = "none",
                       stream_edge: Sequence[int] | None = None,
                       cloud_mesh=None) -> Callable:
    """A compiled ``(params, batches) -> (new_params, loss)`` hybrid step.
    The cut tuple ``(m_s, m_l)``, ``lr``, ``wire``, the stream→edge map
    and the (optional) cloud mesh are static and key the cache — a mesh
    swap recompiles rather than reusing a program lowered for the old
    device set; ``params`` is donated.  jax.jit still specializes on the
    batch-split shapes at first call, so one compiled step serves every
    iteration with the same schedule."""
    cuts = tuple(int(m) for m in m_s)
    edges = tuple(int(e) for e in stream_edge) if stream_edge is not None \
        else (0,) * len(cuts)
    key = ("step", id(model), cuts, int(m_l), float(lr), str(wire), edges,
           None if cloud_mesh is None else id(cloud_mesh))

    def make():
        def step(params: Params, batches):
            return hybrid_sgd_step(model, params, batches, cuts, m_l, lr,
                                   wire=wire, stream_edge=edges,
                                   cloud_mesh=cloud_mesh)
        return jax.jit(_named(step, obs.STEP_PROGRAM), donate_argnums=0)
    return _cached_step(key, model, make)


def jitted_reference_step(model, lr: float) -> Callable:
    """Compiled ``(params, x, y) -> (new_params, loss)`` vanilla SGD step
    (static ``lr``, donated ``params``)."""
    key = ("reference", id(model), float(lr))

    def make():
        def step(params: Params, x: jax.Array, y: jax.Array):
            return reference_sgd_step(model, params, x, y, lr)
        return jax.jit(_named(step, obs.REFERENCE_PROGRAM),
                       donate_argnums=0)
    return _cached_step(key, model, make)


# ---------------------------------------------------------------------------
# Communication accounting: bytes each phase moves across worker boundaries.
# Used by integration tests to confirm the hybrid step's traffic equals the
# cost model's DataSize terms (the other half of model validity).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrafficReport:
    input_bytes: float
    activation_bytes: float   # forward handoff + backward intermediate
    weightgrad_bytes: float   # frontend grads up + averaged grads down

    @property
    def total(self) -> float:
        return self.input_bytes + self.activation_bytes + \
            self.weightgrad_bytes


def traffic(model, sched: Schedule, sample_bytes: float,
            origin: str = "device", wire: str = "none") -> TrafficReport:
    """Bytes one iteration moves across worker boundaries.  The
    activation channel is wire-aware and honors asymmetric fwd/bwd
    dtypes: forward bytes come from ``act_bytes``/``act_elems`` and
    backward bytes from ``grad_bytes``/``grad_elems`` independently, so
    a bf16-fwd/f32-bwd cut is never double-counted at a shared width —
    matching the DES transfer sizes (``MO``/``MG``) term for term."""
    stack = as_layerstack(model)
    metas = stack.cut_meta()
    inp = sum(b * sample_bytes for b, w in
              ((sched.b_o, sched.worker_o), (sched.b_s, sched.worker_s),
               (sched.b_l, sched.worker_l)) if w != origin)
    act = 0.0
    if sched.m_s > 0 and sched.b_s > 0 and sched.worker_s != sched.worker_o:
        m = metas[sched.m_s - 1]
        act += sched.b_s * (wire_act_bytes(m, wire) +
                            wire_grad_bytes(m, wire))
    if sched.m_l > 0 and sched.b_l > 0 and sched.worker_l != sched.worker_o:
        m = metas[sched.m_l - 1]
        act += sched.b_l * (wire_act_bytes(m, wire) +
                            wire_grad_bytes(m, wire))
    wg = 0.0
    if sched.b_s > 0 and sched.worker_s != sched.worker_o:
        wg += 2.0 * sum(m.resolved_param_bytes for m in metas[:sched.m_s])
    if sched.b_l > 0 and sched.worker_l != sched.worker_o:
        wg += 2.0 * sum(m.resolved_param_bytes for m in metas[:sched.m_l])
    return TrafficReport(inp, act, wg)
