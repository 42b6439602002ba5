"""Planner-as-a-service suite: the cross-fleet batched solver must be
bit-identical to the per-fleet engines, the plan-cache fingerprint must
be deterministic across processes and separate near-misses, and the
cache itself must obey its LRU/telemetry contract."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import batched_lp, scheduler
from repro.core.fleet import Fleet
from repro.core.scheduler import MultiSchedulerResult, SolveManyStats, \
    SolveRequest
from repro.serve.planner import (PLAN_CACHE_SIZE, PlanRequest, Planner,
                                 Q_REL, fingerprint, quantize)
from repro.serve.population import synthetic_population

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_stack(seed, K, n_rows, n):
    """A random mixed-status LP stack in the test_batched_lp idiom."""
    rng = np.random.default_rng(seed)
    A_ub = np.zeros((K, n_rows, n))
    b_ub = np.zeros((K, n_rows))
    for k in range(K):
        for r in range(n_rows):
            A_ub[k, r, rng.integers(0, max(1, n - 2))] = \
                rng.uniform(0.0, 2.0)
            A_ub[k, r, (n - 2) + r % 2] = -1.0
        b_ub[k, rng.integers(0, n_rows)] = rng.uniform(-0.5, 4.0)
    A_eq = np.zeros((K, 1, n))
    A_eq[:, 0, :max(1, n - 2)] = 1.0
    b_eq = np.full((K, 1), 8.0)
    c = np.zeros(n)
    c[-2:] = 1.0
    return c, A_ub, b_ub, A_eq, b_eq


def _assert_batch_result_equal(a, b):
    assert np.array_equal(a.x, b.x)
    assert np.array_equal(a.fun, b.fun)
    assert np.array_equal(a.success, b.success)
    assert np.array_equal(a.status, b.status)


# ---------------------------------------------------------------------------
# Fleet axis: heterogeneous stacks through one flattened simplex.
# ---------------------------------------------------------------------------

def test_linprog_batch_many_bitwise_vs_per_stack():
    stacks = [_random_stack(0, 7, 6, 5), _random_stack(1, 3, 4, 8),
              _random_stack(2, 11, 9, 4), _random_stack(3, 1, 6, 6)]
    merged = batched_lp.linprog_batch_many(stacks)
    assert len(merged) == len(stacks)
    for stack, got in zip(stacks, merged):
        ref = batched_lp.linprog_batch(*stack)
        _assert_batch_result_equal(got, ref)


def test_pad_lp_stack_is_inert():
    stack = _random_stack(4, 9, 6, 5)
    padded = batched_lp.pad_lp_stack(*stack, n_pad=11, m_ub_pad=10,
                                     m_eq_pad=3)
    ref = batched_lp.linprog_batch(*stack)
    got = batched_lp.linprog_batch(*padded)
    assert np.array_equal(got.x[:, :5], ref.x)
    assert np.array_equal(got.x[:, 5:], np.zeros((9, 6)))
    assert np.array_equal(got.fun, ref.fun)
    assert np.array_equal(got.status, ref.status)


def test_pad_cells_telemetry():
    stacks = [_random_stack(0, 7, 6, 5), _random_stack(1, 3, 4, 8)]
    native, padded = batched_lp.pad_cells(stacks)
    assert native == 7 * (6 + 1) * 5 + 3 * (4 + 1) * 8
    assert padded == (7 + 3) * (6 + 1) * 8
    assert batched_lp.pad_cells([]) == (0, 0)


def _mixed_requests():
    """3-worker, star and tree fleets (plus a throughput objective) —
    every engine/topology solve_many dispatches over, in one batch."""
    from repro import api
    from repro.models.cnn import lenet5
    reqs = []
    seen = set()
    for r in synthetic_population(n=48, seed=2):
        cls = r.tag.rsplit("/", 1)[0]
        if cls in seen:
            continue
        seen.add(cls)
        _, profile, net, _ = api._prepare(None, r.fleet, None)
        reqs.append(SolveRequest(profile, net, r.B))
    tree = Fleet.from_table2("lenet5", m=4, topology="tree", n_edges=2)
    _, profile, net, _ = api._prepare(lenet5(), tree, None)
    reqs.append(SolveRequest(profile, net, 128))
    reqs.append(SolveRequest(reqs[0].profile, reqs[0].net, reqs[0].B,
                             objective="throughput"))
    return reqs


def test_solve_many_bitwise_vs_per_fleet_engines():
    from repro.core.cost_model import MultiProfile
    reqs = _mixed_requests()
    stats = SolveManyStats()
    got = scheduler.solve_many(reqs, stats=stats)
    ref = [scheduler._solve_multi(r.profile, r.net, r.B,
                                  objective=r.objective)
           if isinstance(r.profile, MultiProfile) else
           scheduler._solve_3w(r.profile, r.net, r.B,
                               objective=r.objective)
           for r in reqs]
    assert stats.n_fleets == len(reqs) and stats.lp_calls >= 1
    for r, g, e in zip(reqs, got, ref):
        assert g.schedule == e.schedule, r
        assert g.t_total == e.t_total          # bitwise, not approx
        assert g.t_period == e.t_period
        assert g.n_lp_solved == e.n_lp_solved
        assert g.n_pruned == e.n_pruned
        if isinstance(g, MultiSchedulerResult):
            assert g.n_lp_refine == e.n_lp_refine
            assert g.refine_rounds == e.refine_rounds


def test_solve_many_rejects_unknown_backend():
    with pytest.raises(ValueError):
        scheduler.solve_many(_mixed_requests()[:1], backend="nope")


# ---------------------------------------------------------------------------
# Fingerprint: determinism, near-miss separation, false-sharing bound.
# ---------------------------------------------------------------------------

def _fp_of(req: PlanRequest) -> str:
    from repro import api
    _, profile, net, wire = api._prepare(req.model, req.fleet, req.wire)
    return fingerprint(profile, net, req.B, req.objective, wire)


def test_quantize_grid():
    # mid-bucket perturbations collapse; > one-bucket jumps separate.
    x = np.array([1.0, 3.7e-3, 250.0])
    assert np.array_equal(quantize(x), quantize(x * (1 + Q_REL / 4)))
    assert not np.array_equal(quantize(x), quantize(x * (1 + 8 * Q_REL)))
    assert np.array_equal(quantize(np.array([0.0])),
                          np.array([0], np.int64))
    assert quantize(np.array([-1.0]))[0] == -quantize(np.array([1.0]))[0]


def test_fingerprint_same_class_same_key():
    reqs = synthetic_population(n=32, seed=5)
    by_class = {}
    for r in reqs:
        by_class.setdefault(r.tag.rsplit("/", 1)[0], []).append(_fp_of(r))
    assert any(len(v) > 1 for v in by_class.values())
    for cls, fps in by_class.items():
        assert len(set(fps)) == 1, cls


def test_fingerprint_near_miss_separates():
    req = synthetic_population(n=8, seed=7)[0]
    base = _fp_of(req)
    prof = req.fleet._profile
    import dataclasses
    bumped = dataclasses.replace(prof, L_f=prof.L_f * (1 + 8 * Q_REL))
    other = PlanRequest(fleet=Fleet.from_profile(bumped,
                                                 req.fleet.network()),
                        B=req.B)
    assert _fp_of(other) != base
    assert _fp_of(PlanRequest(fleet=req.fleet, B=req.B + 1)) != base
    assert _fp_of(PlanRequest(fleet=req.fleet, B=req.B,
                              objective="throughput")) != base


def test_fingerprint_deterministic_across_processes():
    req = synthetic_population(n=8, seed=3)[0]
    here = _fp_of(req)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            from repro import api
            from repro.serve.planner import fingerprint
            from repro.serve.population import synthetic_population
            r = synthetic_population(n=8, seed=3)[0]
            _, profile, net, wire = api._prepare(None, r.fleet, None)
            print(fingerprint(profile, net, r.B, r.objective, wire))
        """)],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", REPO), "JAX_PLATFORMS": "cpu"},
        cwd=REPO)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert out.stdout.strip() == here


def test_false_sharing_bound_on_shared_fingerprint():
    """Two bit-different fleets that share a fingerprint: the cache-hit
    plan, re-scored on the requester's own exact floats, must price
    within the documented (1 + Q_REL)^2 - 1 input blur (~2e-3 rel; we
    pin 5e-3 to leave room for a schedule flip on a knife edge)."""
    from repro import api
    import dataclasses
    req = synthetic_population(n=8, seed=11)[0]
    base = _fp_of(req)
    prof = req.fleet._profile
    shared = None
    for eps in (1e-5, -1e-5, 2e-5, -2e-5, 5e-5, -5e-5, 1e-4, -1e-4):
        cand = PlanRequest(
            fleet=Fleet.from_profile(
                dataclasses.replace(prof, L_f=prof.L_f * (1 + eps)),
                req.fleet.network()),
            B=req.B)
        if not np.array_equal(cand.fleet._profile.L_f, prof.L_f) \
                and _fp_of(cand) == base:
            shared = cand
            break
    assert shared is not None, "no perturbation landed in the bucket"
    planner = Planner()
    cached = planner.plan_many([req, shared])[1]
    assert planner.hits == 1 and planner.misses == 1
    fresh = api.plan(None, shared.fleet, shared.B)
    assert abs(cached.result.t_total - fresh.result.t_total) <= \
        5e-3 * fresh.result.t_total


# ---------------------------------------------------------------------------
# Plan cache: LRU semantics, counters, alias hits, exact re-scoring.
# ---------------------------------------------------------------------------

def _classes(reqs, k):
    """First request of each of k distinct device classes."""
    out, seen = [], set()
    for r in reqs:
        cls = r.tag.rsplit("/", 1)[0]
        if cls not in seen:
            seen.add(cls)
            out.append(r)
        if len(out) == k:
            return out
    raise AssertionError(f"population has < {k} classes")


def test_plan_many_matches_api_plan():
    from repro import api
    reqs = synthetic_population(n=16, seed=0)
    plans = Planner().plan_many(reqs)
    for r, p in zip(reqs, plans):
        ref = api.plan(r.model, r.fleet, r.B, objective=r.objective)
        assert p.result.schedule == ref.result.schedule
        assert p.result.t_total == ref.result.t_total
        assert p.result.t_period == ref.result.t_period
        assert p.result.breakdown == ref.result.breakdown


def test_cache_hits_aliases_and_eviction():
    reqs = synthetic_population(n=64, seed=1)
    distinct = _classes(reqs, 3)
    planner = Planner(cache_size=2)
    planner.plan_many([distinct[0], distinct[0]])   # miss + in-flight alias
    assert (planner.hits, planner.misses) == (1, 1)
    assert len(planner) == 1
    planner.plan_many([distinct[0]])                # warm hit
    assert (planner.hits, planner.misses) == (2, 1)
    planner.plan_many([distinct[1], distinct[2]])   # overflows size-2 LRU
    assert planner.evictions == 1
    assert len(planner) == 2
    st = planner.stats()
    assert st["evictions"] == 1 and st["hit_rate"] == pytest.approx(2 / 5)
    planner.clear()
    assert len(planner) == 0 and planner.hits == 0
    assert planner.stats()["lp_calls"] == 0


def test_cache_hit_is_rescored_not_copied():
    """A hit from a *different* (but fingerprint-identical) requester
    keeps its own exact pricing — t_total recomputed from the hit
    request's floats, search_log dropped."""
    reqs = synthetic_population(n=64, seed=1)
    r = _classes(reqs, 1)[0]
    twin = [q for q in reqs
            if q.tag.rsplit("/", 1)[0] == r.tag.rsplit("/", 1)[0]][1]
    planner = Planner()
    p0, p1 = planner.plan_many([r, twin])
    assert p1.result.schedule == p0.result.schedule
    assert p1.result.t_total == p0.result.t_total   # identical fleets
    assert p1.result.search_log == []


def test_default_planner_roundtrip_and_api_reexport():
    import repro
    from repro.serve.planner import clear_plan_cache, _DEFAULT_PLANNER
    clear_plan_cache()
    reqs = synthetic_population(n=8, seed=0)[:2]
    plans = repro.plan_many(reqs)
    assert len(plans) == 2
    assert _DEFAULT_PLANNER.misses >= 1
    clear_plan_cache()
    assert len(_DEFAULT_PLANNER) == 0
    assert PLAN_CACHE_SIZE >= 1024


def test_admission_loop_submit_drain():
    reqs = synthetic_population(n=8, seed=0)
    planner = Planner(max_batch=2)
    for r in reqs:
        planner.submit(r)
    plans = planner.drain()
    assert len(plans) == len(reqs)
    assert planner.drain() == []
    ref = Planner().plan_many(reqs)
    for a, b in zip(plans, ref):
        assert a.result.schedule == b.result.schedule
        assert a.result.t_total == b.result.t_total


def test_bench_entry_smoke(capsys):
    from repro.serve import planner as planner_mod
    rc = planner_mod.main(["--bench", "--n", "32", "--seed", "0",
                           "--assert-hit-rate"])
    assert rc == 0
    assert "plans/s" in capsys.readouterr().out
