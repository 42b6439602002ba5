"""Distribution layer: sharding-rule properties (hypothesis) on abstract
meshes, plus multi-device semantics tests (tiered sync equivalence,
dry-run micro-cell) run in a subprocess so this pytest process keeps its
single CPU device."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from tests._compat import given, settings, st

from repro.distrib.sharding import batch_spec, cache_spec, param_spec

# An AbstractMesh carries axis names/sizes without real devices — the
# sharding rules only read those.
MESH = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
SINGLE = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@settings(max_examples=80, deadline=None)
@given(shape=st.lists(st.sampled_from(
    [1, 2, 3, 8, 16, 32, 60, 112, 128, 151936, 4096]),
    min_size=1, max_size=4).map(tuple))
def test_param_spec_properties(shape):
    for mesh in (MESH, SINGLE):
        spec = param_spec(mesh, shape)
        assert len(spec) in (0, len(shape))
        used = [a for a in spec if a is not None]
        assert len(set(used)) == len(used), "axis used twice"
        for i, a in enumerate(spec):
            if a is None:
                continue
            assert shape[i] % mesh.shape[a] == 0, (shape, spec)
        if len(shape) >= 3:
            assert spec and spec[0] is None, "layer-stack dim sharded"


@settings(max_examples=50, deadline=None)
@given(batch=st.sampled_from([1, 2, 16, 32, 128, 256, 255]),
       ndim=st.integers(1, 4))
def test_batch_spec_divisibility(batch, ndim):
    for mesh in (MESH, SINGLE):
        spec = batch_spec(mesh, batch, ndim)
        if spec[0] is not None:
            names = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
            prod = int(np.prod([mesh.shape[a] for a in names]))
            assert batch % prod == 0


@pytest.mark.parametrize("axis_type,constrained", [
    (jax.sharding.AxisType.Auto, True),
    (jax.sharding.AxisType.Explicit, False)])
def test_shard_hint_constrains_auto_axes(axis_type, constrained):
    """``shard_hint`` names only ``Auto`` mesh axes: under one it emits a
    constraint on that axis, under an ``Explicit`` one it names none."""
    import jax.numpy as jnp

    from repro.models.lm.common import shard_hint
    mesh = jax.make_mesh((1,), ("data",), axis_types=(axis_type,))
    with jax.set_mesh(mesh):
        txt = jax.jit(lambda x: shard_hint(x * 2, "data", None)).lower(
            jnp.ones((4, 4))).as_text()
    assert ('[{"data"}, {}]' in txt) == constrained, txt


def test_cache_spec_kv_vs_seq():
    # kv=16 divisible -> heads TP; kv=1 (MQA) -> sequence-sharded
    s = cache_spec(SINGLE, (24, 128, 32768, 16, 128), 128)
    assert s[3] == "model" and s[2] is None
    s = cache_spec(SINGLE, (52, 128, 32768, 1, 128), 128)
    assert s[2] == "model" and s[3] is None


def test_batch_spec_global_batch_one_replicated():
    from jax.sharding import PartitionSpec as P
    # long_500k-style global_batch=1: indivisible by every DP axis ->
    # fully replicated on both mesh layouts
    for mesh in (MESH, SINGLE):
        assert batch_spec(mesh, 1, 2) == P(None, None)
    # divisible by data (16) but not pod*data (32) -> data-only fallback
    assert batch_spec(MESH, 16, 2) == P("data", None)
    # divisible by the full DP product -> (pod, data) on the lead dim
    assert batch_spec(MESH, 64, 3) == P(("pod", "data"), None, None)


def test_cache_spec_kv_one_full_spec():
    from jax.sharding import PartitionSpec as P
    # granite-style MQA cache [L, B, S, kv=1, hd]: the KV-head dim can't
    # carry model=16, so the sequence dim does; batch rides the DP axes
    assert cache_spec(MESH, (40, 32, 4096, 1, 64), 32) == \
        P(None, ("pod", "data"), "model", None, None)
    # with enough KV heads the head dim carries TP and S stays whole
    assert cache_spec(MESH, (40, 32, 4096, 16, 64), 32) == \
        P(None, ("pod", "data"), None, "model", None)


def test_param_spec_stacked_leaf_rule():
    from jax.sharding import PartitionSpec as P
    # scanned [L, in, out] leaf: the stack dim is never sharded; TP goes
    # to the larger of (in, out), FSDP to the other
    assert param_spec(SINGLE, (24, 4096, 1024)) == P(None, "model", "data")
    assert param_spec(SINGLE, (24, 1024, 4096)) == P(None, "data", "model")
    # TP-only mode replicates the would-be FSDP dim
    assert param_spec(SINGLE, (24, 1024, 4096), fsdp=False) == \
        P(None, None, "model")
    # a dim indivisible by the axis falls through to the next candidate
    assert param_spec(SINGLE, (24, 151, 4096)) == P(None, None, "model")


def test_int8_sync_bytes_single_source():
    """Predicted DCN sync bytes (``choose_tiers``/``dcn_bytes_per_step``)
    and the bytes the int8 all-gather actually ships (payload + per-row
    f32 scales) both come from ``repro.core.wire.int8_leaf_bytes``."""
    import jax.numpy as jnp
    from repro.core.wire import int8_leaf_bytes
    from repro.distrib.tiered_sync import (_as_2d, choose_tiers,
                                           dcn_bytes_per_step)
    from repro.kernels import ops as kops
    shapes = {"w2d": (64, 32), "b1d": (128,), "stack3d": (4, 16, 8)}
    arrs = {k: jax.random.normal(jax.random.PRNGKey(i), s)
            for i, (k, s) in enumerate(shapes.items())}
    # measured: what _compressed_mean ships per pod for one leaf
    for k, a in arrs.items():
        a2, _ = _as_2d(a)
        q, scale = kops.quantize_int8(a2, jax.random.PRNGKey(9))
        assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
        measured = q.size * q.dtype.itemsize + \
            scale.size * scale.dtype.itemsize
        assert measured == int8_leaf_bytes(a.shape), k
    # predicted: the tier chooser and the diagnostics helper charge the
    # same per-leaf formula (regression: the old inline ``bytes/4``
    # estimate dropped the row scales)
    pshapes = jax.eval_shape(lambda: arrs)
    tiers = choose_tiers(pshapes, n_pods=2, dcn_bytes_per_s=1.0,
                         compute_seconds=1e-12)    # force all-int8
    assert all(jax.tree.leaves(tiers.quantized))
    want_wire = sum(int8_leaf_bytes(s) for s in shapes.values())
    assert tiers.back_wire_bytes == want_wire
    gather = 0.5                                   # (P-1)/P at P=2
    assert dcn_bytes_per_step(tiers, 2) == want_wire * gather
    assert tiers.sync_seconds == want_wire * gather    # dcn = 1 B/s


def _run_subprocess(code: str):
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", REPO),
             # the image ships libtpu: without an explicit platform pin
             # jax probes for TPU hardware for minutes before falling
             # back to CPU (the parent test env pins it too).
             "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_tiered_sync_equivalence_multidev():
    """On a real 8-device (2-pod) mesh: tiers=None tiered sync == global
    pmean bit-for-bit; int8 tier stays within one quantization step."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distrib.tiered_sync import (choose_tiers,
                                               tiered_grad_sync)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        grads = {"big": jax.random.normal(jax.random.PRNGKey(0),
                                          (8, 64, 32)),
                 "small": jax.random.normal(jax.random.PRNGKey(1), (8, 8))}

        def sync(g, key, tiers):
            def per_pod(g, key):
                key = jax.random.fold_in(key, jax.lax.axis_index("pod"))
                return tiered_grad_sync(g, tiers, key, axis="pod")
            # check_vma=False as in the production step: the compressed
            # path's output is replicated by construction (identical
            # all-gather + arithmetic on every pod) but not provably so.
            return jax.shard_map(per_pod, in_specs=(P("pod"), P()),
                                 out_specs=P(), axis_names={"pod"},
                                 check_vma=False)(g, key)

        key = jax.random.PRNGKey(42)
        with jax.set_mesh(mesh):
            plain = jax.jit(lambda g, k: sync(g, k, None))(grads, key)
            want = jax.tree.map(
                lambda g: g.reshape(2, 4, *g.shape[1:]).mean(0), grads)
            for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6)

            shapes = jax.eval_shape(lambda: grads)
            tiers = choose_tiers(shapes, n_pods=2, dcn_bytes_per_s=1.0,
                                 compute_seconds=1e-12)  # force all-int8
            assert all(jax.tree.leaves(tiers.quantized))
            q = jax.jit(lambda g, k: sync(g, k, tiers))(grads, key)
            for name in ("big", "small"):
                per_pod = grads[name].reshape(2, 4, *grads[name].shape[1:])
                exact = per_pod.mean(0)
                step = np.abs(np.asarray(per_pod)).max() / 127.0
                err = np.abs(np.asarray(q[name]) - np.asarray(exact))
                assert err.max() <= step + 1e-6, (name, err.max(), step)
        print("OK")
    """)


def test_tree_sharded_cloud_tier_multidev():
    """Tree hybrid step with the cloud tail under ``shard_map`` on a real
    8-device mesh: matches the unsharded tree step to f32 tolerance (the
    psum reorders reductions, so not bitwise) and enforces batch
    divisibility by the dp shard count."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.cost_model import MultiSchedule
        from repro.core.hybrid_step import tree_hybrid_step_from_schedule
        from repro.models.cnn import DenseSpec, LayeredModel

        specs = tuple(DenseSpec(f"fc{i}", 16) for i in range(4)) + \\
            (DenseSpec("out", 5, relu=False),)
        model = LayeredModel("tiny_mlp", specs, (8,), 5)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        sched = MultiSchedule(
            worker_o="cloud", worker_l="device_3",
            s_workers=("device_0", "device_1", "device_2", "edge_0",
                       "edge_1"),
            m_s=(2, 2, 1, 2, 1), m_l=3, b_o=6, b_s=(4, 3, 3, 5, 3), b_l=0)
        eo = (0, 0, 1, 0, 1)
        kx, ky = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (24, 8), jnp.float32)
        y = jax.random.randint(ky, (24,), 0, 5)
        params = model.init(jax.random.PRNGKey(1))
        p_ref, l_ref = tree_hybrid_step_from_schedule(
            model, params, x, y, sched, lr=0.05, stream_edge=eo)
        p_sh, l_sh = tree_hybrid_step_from_schedule(
            model, params, x, y, sched, lr=0.05, stream_edge=eo,
            cloud_mesh=mesh)
        np.testing.assert_allclose(float(l_ref), float(l_sh), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)

        # B=24 divides the 4 dp shards; a 23-sample split must not
        bad = MultiSchedule(
            worker_o="cloud", worker_l="device_3",
            s_workers=sched.s_workers, m_s=sched.m_s, m_l=3,
            b_o=5, b_s=(4, 3, 3, 5, 3), b_l=0)
        try:
            tree_hybrid_step_from_schedule(
                model, params, x[:23], y[:23], bad, lr=0.05,
                stream_edge=eo, cloud_mesh=mesh)
            raise SystemExit("divisibility guard did not fire")
        except ValueError as e:
            assert "divisible" in str(e), e
        print("OK")
    """)


def test_hier_step_keeps_intra_pod_parallelism():
    """The hier_sync step makes only ``pod`` manual: inside a pod the
    model stays split over ``data``/``model``, so each chip does the same
    FLOPs as under the plain data-parallel step.  Making every axis
    manual would replicate a pod's whole batch on each of its chips (4x
    here)."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.distrib import (batch_shardings, opt_state_shardings,
                                   param_shardings)
        from repro.launch.hlo_analysis import loop_aware_cost
        from repro.models.lm.model import build_model
        from repro.optim import get_optimizer
        from repro.train.step import make_train_step

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        model = build_model(get_arch("qwen2.5-3b").smoke)
        opt = get_optimizer("adamw")
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        oshapes = jax.eval_shape(opt.init, pshapes)
        state = {"params": pshapes, "opt": oshapes}
        sshard = {"params": param_shardings(mesh, pshapes),
                  "opt": opt_state_shardings(mesh, oshapes)}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        bshard = batch_shardings(mesh, batch)
        flops = {}
        for hier in (False, True):
            step = make_train_step(model, opt, hier_sync=hier)
            with jax.set_mesh(mesh):
                compiled = jax.jit(
                    step, in_shardings=(sshard, bshard,
                                        NamedSharding(mesh, P())),
                    out_shardings=(sshard, None)).lower(
                        state, batch,
                        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
            flops[hier] = loop_aware_cost(compiled.as_text())[0]
        assert flops[False] > 0
        assert flops[True] <= 1.25 * flops[False], flops
        print("OK")
    """)


def test_dryrun_micro_cell():
    """A miniature dry-run (8 devices, smoke-scale arch) exercises the
    full lower->compile->analyse path including the hier tiered step."""
    _run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch
        from repro.distrib import (batch_shardings, choose_tiers,
                                   opt_state_shardings, param_shardings)
        from repro.models.lm.model import build_model
        from repro.optim import get_optimizer
        from repro.train.step import make_train_step
        from repro.launch.hlo_analysis import loop_aware_cost

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        cfg = get_arch("qwen2.5-3b").smoke
        model = build_model(cfg)
        opt = get_optimizer("adamw")
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        oshapes = jax.eval_shape(opt.init, pshapes)
        state = {"params": pshapes, "opt": oshapes}
        sshard = {"params": param_shardings(mesh, pshapes),
                  "opt": opt_state_shardings(mesh, oshapes)}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        bshard = batch_shardings(mesh, batch)
        tiers = choose_tiers(pshapes, n_pods=2, dcn_bytes_per_s=1e3,
                             compute_seconds=1e-9)
        step = make_train_step(model, opt, microbatches=2, hier_sync=True,
                               tiers=tiers)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(sshard, bshard,
                                                 NamedSharding(mesh, P())),
                             out_shardings=(sshard, None))
            lowered = jitted.lower(state, batch,
                                   jax.ShapeDtypeStruct((2,), jnp.uint32))
            compiled = lowered.compile()
            txt = compiled.as_text()
            assert "all-gather" in txt or "all-reduce" in txt
            f, b, c = loop_aware_cost(txt)
            assert f > 0 and b > 0
            ma = compiled.memory_analysis()
            assert ma.temp_size_in_bytes >= 0
        print("OK")
    """)
