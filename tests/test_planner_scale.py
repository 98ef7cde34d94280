"""Vectorized planner-engine tests: the batched cost-model sweep, the
max-plus DP solver, and the incremental PlanTable must agree with the
scalar reference paths they replaced."""
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import costmodel, waf
from repro.core.costmodel import A800, TPU_V5E, TaskModel
from repro.core.planner import (PlanInput, PlannerCache, PlanTable,
                                _maxplus, _maxplus_vals,
                                _maxplus_vals_fused, brute_force, solve,
                                solve_reference)
from repro.core.waf import Task

SIZES = ["gpt3-1.3b", "gpt3-7b", "gpt3-13b", "gpt3-70b"]


def _task(size="gpt3-1.3b", weight=1.0, gb=256, cap=None):
    return Task(model=TaskModel.from_arch(get_arch(size), global_batch=gb),
                weight=weight, max_workers=cap)


def _tasks(m, caps=None):
    return [_task(SIZES[i % len(SIZES)], weight=0.5 + 0.1 * i,
                  gb=128 if i % 2 else 256,
                  cap=caps[i] if caps else None) for i in range(m)]


def _inp(tasks, assignment, n, d_run=3600.0, d_tr=120.0, faulted=None):
    faulted = faulted or (False,) * len(tasks)
    return PlanInput(tuple(tasks), tuple(assignment), n, d_run, d_tr,
                     tuple(faulted))


# ---- (a) throughput_curve vs per-x scalar reference -----------------------


@pytest.mark.parametrize("hw", [A800, TPU_V5E], ids=lambda h: h.name)
@pytest.mark.parametrize("size", SIZES + ["gpt3-175b"])
def test_throughput_curve_matches_scalar(hw, size):
    t = TaskModel.from_arch(get_arch(size), seq_len=2048, global_batch=256)
    n = 192
    curve = costmodel.throughput_curve(t, n, hw)
    assert curve.flops.shape == (n + 1,)
    assert curve.flops[0] == 0.0
    for x in range(n + 1):
        ref = costmodel.achieved_flops(t, x, hw)
        assert curve.flops[x] == pytest.approx(ref, rel=1e-12, abs=0.0), x
        p = curve.plan(x)
        if ref == 0.0:
            assert p is None
        else:
            assert p is not None
            assert p.agg_flops == pytest.approx(ref, rel=1e-12)
            assert p.dp * p.tp * p.pp <= max(x, 0)
            assert p.mem_per_worker <= hw.hbm_bytes


@pytest.mark.parametrize("hw", [A800, TPU_V5E], ids=lambda h: h.name)
@pytest.mark.parametrize("size", ["gpt3-7b", "gpt3-175b"])
def test_min_feasible_matches_linear_scan(hw, size):
    t = TaskModel.from_arch(get_arch(size), global_batch=256)
    assert (costmodel.min_feasible_workers(t, hw)
            == costmodel.min_feasible_workers_reference(t, hw))


def test_curve_memoized_and_growable():
    t = TaskModel.from_arch(get_arch("gpt3-1.3b"), global_batch=256)
    small = costmodel.throughput_curve(t, 16, A800)
    big = costmodel.throughput_curve(t, 64, A800)
    assert np.array_equal(big.flops[:17], small.flops)
    again = costmodel.throughput_curve(t, 64, A800)
    assert again.flops is big.flops or np.shares_memory(again.flops,
                                                        big.flops)


def test_waf_curve_matches_scalar():
    t = _task("gpt3-7b", weight=1.3)
    n = 64
    F = waf.waf_curve(t, n, A800)
    for x in range(n + 1):
        assert F[x] == pytest.approx(waf.waf(t, x, A800), rel=1e-12, abs=0.0)


def test_reward_curve_matches_scalar():
    t = _task("gpt3-1.3b", weight=0.8)
    n = 48
    for faulted in (False, True):
        g = waf.reward_curve(t, 16, n, d_running=3600.0, d_transition=120.0,
                             worker_faulted=faulted, hw=A800)
        for k in range(n + 1):
            ref = waf.reward(t, 16, k, d_running=3600.0, d_transition=120.0,
                             worker_faulted=faulted, hw=A800)
            assert g[k] == pytest.approx(ref, rel=1e-12, abs=1e-9), (faulted, k)


# ---- (b) vectorized solve vs brute force / scalar DP ----------------------


def test_maxplus_matches_naive():
    rng = np.random.RandomState(0)
    for _ in range(50):
        n = rng.randint(0, 24)
        prev = rng.uniform(-5, 5, n + 1)
        g = rng.uniform(-5, 5, n + 1)
        out, ch = _maxplus(prev, g)
        for j in range(n + 1):
            vals = [prev[j - k] + g[k] for k in range(j + 1)]
            assert out[j] == max(vals)
            assert ch[j] == int(np.argmax(vals))


def test_solve_matches_brute_force_small():
    tasks = _tasks(3)
    for n, faulted in [(10, (False,) * 3), (12, (True, False, False))]:
        inp = _inp(tasks, [4, 4, 4], n, faulted=faulted)
        got = solve(inp, A800)
        want = brute_force(inp, A800)
        assert got.total_reward == pytest.approx(want.total_reward, rel=1e-9)
        assert sum(got.assignment) <= n


@pytest.mark.parametrize("m,n", [(4, 48), (8, 96)])
def test_solve_matches_scalar_dp_medium(m, n):
    tasks = _tasks(m)
    per = n // m
    for fi in (None, 0, m - 1):
        faulted = tuple(i == fi for i in range(m))
        inp = _inp(tasks, [per] * m, n - 8 if fi is not None else n,
                   faulted=faulted)
        got = solve(inp, A800)
        want = solve_reference(inp, A800)
        assert got.total_reward == pytest.approx(want.total_reward, rel=1e-9)
        assert got.assignment == want.assignment
        assert got.waf == pytest.approx(want.waf, rel=1e-9)


def test_solve_equals_reference_on_random_tables():
    """Hypothesis-free randomized sweep: the vectorized DP and the scalar
    DP are the same function on arbitrary (non-monotone) reward rows."""
    rng = np.random.RandomState(42)

    class _Row:
        max_workers = None              # Task contract: uncapped

        def __init__(self, row):
            self.row = row

        def necessary(self, hw):        # waf() sees an unmeetable floor
            return 10 ** 9              # -> cluster WAF contribution 0

    import repro.core.planner as planner_mod
    for trial in range(60):
        m = rng.randint(1, 5)
        n = rng.randint(0, 12)
        rows = rng.uniform(0, 100, (m, n + 1))
        inp = _inp([_Row(r) for r in rows], [0] * m, n)
        def table_row(i_, idx, hw):
            return list(rows[idx])

        orig = planner_mod._reward_row
        try:
            planner_mod._reward_row = table_row
            got = solve(inp, A800)
            want = solve_reference(inp, A800)
        finally:
            planner_mod._reward_row = orig
        assert got.total_reward == pytest.approx(want.total_reward,
                                                 rel=1e-12), trial
        assert got.assignment == want.assignment, trial


def test_fused_kernel_bitwise_identical_to_plain():
    """The tiled fused add+max kernel (both orientations) reduces exactly
    the candidate set of ``_maxplus_vals`` — outputs are bitwise equal."""
    rng = np.random.RandomState(3)
    for _ in range(120):
        n = rng.randint(0, 70)
        prev = rng.uniform(-5, 5, n + 1)
        g = rng.uniform(-5, 5, n + 1)
        want = _maxplus_vals(prev, g)
        assert np.array_equal(want, _maxplus_vals_fused(prev, g))
        assert np.array_equal(want, _maxplus_vals_fused(prev, g, block=4))


def test_banded_kernel_bitwise_identical_under_contract():
    """With monotone prev and g flat past the band — the invariants the
    planner guarantees — the banded kernel equals the dense one bitwise,
    at every band including 0 and n."""
    rng = np.random.RandomState(4)
    for _ in range(120):
        n = rng.randint(1, 70)
        cap = rng.randint(0, n + 1)
        prev = np.maximum.accumulate(rng.uniform(-5, 5, n + 1))
        g = rng.uniform(-5, 5, n + 1)
        g[cap:] = g[cap]
        want = _maxplus_vals(prev, g)
        assert np.array_equal(want, _maxplus_vals_fused(prev, g, band=cap))


def test_waf_flat_past_cap_matches_scalar():
    """Capped tasks: the vector F(t, ·) is flat past the cap and equal to
    the scalar ``waf`` (which clamps x) at every x — including a cap
    below the requirement floor (the task can then never run)."""
    for cap in (0, 4, 12, 64, None):
        t = _task("gpt3-7b", weight=1.1, cap=cap)
        F = waf.waf_curve(t, 96, A800)
        for x in range(97):
            assert F[x] == pytest.approx(waf.waf(t, x, A800),
                                         rel=1e-12, abs=0.0), (cap, x)
        if cap is not None and cap < 96:
            assert np.all(F[cap:] == F[min(cap, 96)])
    M = waf.waf_matrix([_task(cap=8), _task("gpt3-7b", cap=2)], 64, A800)
    for i, t in enumerate([_task(cap=8), _task("gpt3-7b", cap=2)]):
        for x in range(65):
            assert M[i, x] == pytest.approx(waf.waf(t, x, A800),
                                            rel=1e-12, abs=0.0)


# ---- (c) incremental PlanTable vs scenario-by-scenario solves -------------


@pytest.mark.parametrize("m,n", [(1, 8), (3, 36), (6, 96)])
def test_incremental_table_matches_full_solves(m, n):
    tasks = _tasks(m)
    assignment = [n // m] * m
    inc = PlanTable(tasks, assignment, A800, 3600.0, 120.0)
    ref = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    assert set(inc.table) == set(ref.table)
    n_now = sum(assignment)
    for key in ref.table:
        a, b = inc.table[key], ref.table[key]
        assert a.total_reward == pytest.approx(b.total_reward,
                                               rel=1e-9), key
        budget = {"join:1": n_now + inc.workers_per_fault}.get(
            key, n_now if key.startswith("finish") else
            max(n_now - inc.workers_per_fault, 0))
        assert sum(a.assignment) <= budget, (key, a)
        expect_len = m - 1 if key.startswith("finish") else m
        assert len(a.assignment) == expect_len


def test_empty_task_set_table():
    table = PlanTable([], [], A800, 3600.0, 120.0)
    ref = PlanTable([], [], A800, 3600.0, 120.0, incremental=False)
    assert set(table.table) == set(ref.table) == {"join:1"}
    assert table.table["join:1"].assignment == ()
    assert table.table["join:1"].total_reward == 0.0


def test_incremental_table_dispatch_is_constant_time():
    tasks = _tasks(4)
    table = PlanTable(tasks, [8, 8, 8, 8], A800, 3600.0, 120.0)
    assert table.lookup("fault:0") is not None
    assert table.lookup("join:1") is not None
    assert table.lookup("finish:3") is not None
    assert table.lookup("nonsense") is None


def test_solve_fast_identical_to_solve():
    """The cached engine's fresh-dispatch solver is the same function as
    ``solve`` — identical assignments AND rewards, bit for bit."""
    from repro.core.planner import solve_fast
    for m, n in [(1, 8), (4, 48), (8, 96)]:
        tasks = _tasks(m)
        for fi in (None, 0, m - 1):
            faulted = tuple(i == fi for i in range(m))
            inp = _inp(tasks, [n // m] * m, n, faulted=faulted)
            a, b = solve(inp, A800), solve_fast(inp, A800)
            assert a.assignment == b.assignment
            assert a.total_reward == b.total_reward


# ---- (d) lazy / cross-rebuild-cached PlanTable ----------------------------


def test_lazy_cached_table_identical_to_eager():
    """Every scenario assembled lazily through a shared PlannerCache is
    bit-identical (assignment AND reward) to the eager uncached build."""
    tasks = _tasks(6)
    cache = PlannerCache()
    assignment = [16, 16, 16, 24, 24, 32]
    for budget in (None, 160):
        eager = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                          n_budget=budget)
        lazy = cache.table(tasks, assignment, A800, 3600.0, 120.0,
                           n_budget=budget)
        assert not lazy.table                 # nothing assembled yet
        for key in eager.table:
            a, b = eager.table[key], lazy.lookup(key)
            assert a.assignment == b.assignment, key
            assert a.total_reward == b.total_reward, key
    # recurring state: the cache returns the same (now warm) table object
    again = cache.table(tasks, assignment, A800, 3600.0, 120.0,
                        n_budget=160)
    assert again.lookup("fault:0") is lazy.lookup("fault:0")
    assert cache.stats()["hits"]["tables"] >= 1


def test_cached_table_matches_reference_under_random_churn():
    """Deterministic churn walk: one task's assignment changes per step
    (the cross-rebuild chain-reuse case), and every scenario of every
    intermediate state must match the all-scalar reference table."""
    import random

    rng = random.Random(0)
    m, n_budget = 3, 28
    tasks = _tasks(m)
    cache = PlannerCache()
    assignment = [8, 8, 8]
    for step in range(6):
        lazy = cache.table(tasks, assignment, A800, 3600.0, 120.0,
                           workers_per_fault=4, n_budget=n_budget)
        ref = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                        workers_per_fault=4, incremental=False,
                        solver=solve_reference)
        for key in ref.table:
            got = lazy.lookup(key)
            want = ref.table[key]
            assert got.total_reward == pytest.approx(
                want.total_reward, rel=1e-9), (step, key, assignment)
        i = rng.randrange(m)
        assignment[i] = rng.choice([4, 8, 12, 16])
    stats = cache.stats()
    assert stats["hits"]["arrays"] > 0        # chains were reused


# ---- (e) segment-tree engine ----------------------------------------------


@pytest.mark.parametrize("engine", ["segtree", "batched"])
@pytest.mark.parametrize("m,n,caps", [
    (1, 8, [None]), (2, 16, [6, None]), (3, 36, [10, None, 8]),
    (5, 60, [12, 12, None, 4, 50]), (6, 96, [None] * 6)])
def test_segtree_table_matches_reference(m, n, caps, engine):
    """Tree-based tables (per-node segtree and the default
    level-synchronous batched engine) match the all-scalar reference on
    capped and uncapped fleets, with feasible tracebacks: the traced
    assignment's scalar reward re-sums to the DP total."""
    tasks = _tasks(m, caps=caps)
    assignment = [n // m] * m
    seg = PlanTable(tasks, assignment, A800, 3600.0, 120.0, engine=engine)
    assert seg.engine == engine
    ref = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    assert set(seg.table) == set(ref.table)
    n_now = sum(assignment)
    w = seg.workers_per_fault
    for key in ref.table:
        a, b = seg.table[key], ref.table[key]
        assert a.total_reward == pytest.approx(b.total_reward,
                                               rel=1e-9), key
        budget = {"join:1": n_now + w}.get(
            key, n_now if key.startswith("finish")
            else max(n_now - w, 0))
        assert sum(a.assignment) <= budget, (key, a)
        # traceback consistency: re-score the plan with the scalar reward
        kind, _, idx = key.partition(":")
        if kind == "finish":
            rem = [(t, assignment[i]) for i, t in enumerate(tasks)
                   if i != int(idx)]
        else:
            rem = list(zip(tasks, assignment))
        total = sum(waf.reward(
            t, x_old, x_new, d_running=3600.0, d_transition=120.0,
            worker_faulted=(kind == "fault" and i == int(idx)), hw=A800)
            for i, ((t, x_old), x_new) in enumerate(zip(rem, a.assignment)))
        assert total == pytest.approx(a.total_reward, rel=1e-9), key


def test_segtree_lazy_cached_identical_to_eager():
    """Lazy cache-assembled segment-tree scenarios are bit-identical to
    the eager uncached build (same node merges, same kernel)."""
    tasks = _tasks(5, caps=[8, None, 12, None, 6])
    cache = PlannerCache()
    assignment = [12, 12, 12, 12, 12]
    eager = PlanTable(tasks, assignment, A800, 3600.0, 120.0)
    lazy = cache.table(tasks, assignment, A800, 3600.0, 120.0)
    for key in eager.table:
        got = lazy.lookup(key)
        assert got.assignment == eager.table[key].assignment, key
        assert got.total_reward == eager.table[key].total_reward, key


def test_segtree_and_chain_engines_agree():
    """Both incremental engines implement the same optimum: totals agree
    to float-reassociation tolerance on every scenario."""
    tasks = _tasks(7, caps=[16, None, 8, 24, None, 12, 16])
    assignment = [12] * 7
    seg = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    engine="segtree")
    chain = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                      engine="chain")
    assert set(seg.table) == set(chain.table)
    for key in seg.table:
        assert seg.table[key].total_reward == pytest.approx(
            chain.table[key].total_reward, rel=1e-9), key


@pytest.mark.parametrize("engine", ["segtree", "batched"])
def test_segtree_cached_churn_reuses_log_m_nodes(engine):
    """A one-task churn step through a shared cache recomputes only the
    O(log m) tree nodes whose span contains the change (plus the
    complements crossing them) — most array lookups are hits.  Holds for
    the per-node segtree engine and the level-synchronous batched one
    (same content-keyed node/complement cache entries)."""
    m = 8
    tasks = _tasks(m, caps=[12] * m)
    cache = PlannerCache()
    assignment = [8] * m
    t1 = cache.table(tasks, assignment, A800, 3600.0, 120.0, n_budget=80,
                     engine=engine)
    for key in t1.scenario_keys():
        t1.lookup(key)
    before = dict(cache.misses)
    assignment[3] = 12
    t2 = cache.table(tasks, assignment, A800, 3600.0, 120.0, n_budget=80,
                     engine=engine)
    for key in t2.scenario_keys():
        t2.lookup(key)
    new_arrays = cache.misses["arrays"] - before["arrays"]
    # full from-scratch assembly costs > 3 arrays per scenario; the
    # cached rebuild must reuse far more than it recomputes
    assert new_arrays < 2 * len(t2.scenario_keys()), new_arrays
    ref = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    for key in ref.table:
        assert t2.lookup(key).total_reward == pytest.approx(
            ref.table[key].total_reward, rel=1e-9), key


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        PlanTable(_tasks(1), [4], A800, 3600.0, 120.0, engine="btree")


# ---- (f) level-synchronous batched engine ---------------------------------


def test_batched_kernel_bitwise_identical_per_slice():
    """The stacked kernel with per-row bands equals per-slice 2-D fused
    calls bitwise, across mixed dense/banded rows and every strategy
    bucket (shift-slab stacks and per-row tile fallthrough)."""
    from repro.core.planner import _maxplus_vals_fused_batched
    rng = np.random.RandomState(7)
    for _ in range(120):
        B = rng.randint(1, 10)
        n = rng.randint(0, 70)
        prev = np.maximum.accumulate(rng.uniform(-5, 5, (B, n + 1)),
                                     axis=1)
        g = rng.uniform(-5, 5, (B, n + 1))
        bands = []
        for r in range(B):
            b = rng.choice([None, rng.randint(0, n + 1)])
            if b is not None:
                b = int(b)
                g[r, b:] = g[r, min(b, n)]
            bands.append(b)
        out = _maxplus_vals_fused_batched(prev, g, bands)
        for r in range(B):
            want = _maxplus_vals_fused(prev[r], g[r], band=bands[r])
            assert np.array_equal(out[r], want), (B, n, bands, r)


@pytest.mark.parametrize("m,n,caps", [
    (1, 8, [None]), (3, 36, [10, None, 8]), (6, 96, [12] * 6),
    (7, 96, [16, None, 8, 24, None, 12, 16])])
def test_batched_engine_bitwise_identical_to_segtree(m, n, caps):
    """The level-synchronous engine stacks exactly the segtree's node
    merges (same operands, orders and bands), so eager tables agree
    bit for bit — totals AND assignments."""
    tasks = _tasks(m, caps=caps)
    assignment = [n // m] * m
    bat = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    engine="batched")
    seg = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    engine="segtree")
    assert set(bat.table) == set(seg.table)
    for key in seg.table:
        assert bat.table[key].total_reward == seg.table[key].total_reward
        assert bat.table[key].assignment == seg.table[key].assignment
        assert bat.table[key].waf == seg.table[key].waf


def test_batched_value_only_rebuild_with_lazy_traceback():
    """``rebuild_values`` materializes every scenario's total with ZERO
    tracebacks; a subsequent ``lookup`` runs exactly one traceback for
    the dispatched key and its plan matches the eager build bitwise."""
    tasks = _tasks(5, caps=[8, None, 12, None, 6])
    assignment = [12] * 5
    cache = PlannerCache()
    eager = PlanTable(tasks, assignment, A800, 3600.0, 120.0)
    lazy = cache.table(tasks, assignment, A800, 3600.0, 120.0)
    totals = lazy.rebuild_values()
    assert lazy.batch_stats["tracebacks"] == 0
    assert not lazy.table                    # values only, no Plans yet
    assert set(totals) == set(eager.table)
    for key, total in totals.items():
        assert total == eager.table[key].total_reward, key
        assert lazy.scenario_total(key) == total, key
    plan = lazy.lookup("fault:2")
    assert lazy.batch_stats["tracebacks"] == 1
    assert plan.assignment == eager.table["fault:2"].assignment
    assert plan.total_reward == eager.table["fault:2"].total_reward
    # memoized Plan: a second lookup is a dict hit, not a new traceback
    assert lazy.lookup("fault:2") is plan
    assert lazy.batch_stats["tracebacks"] == 1


@pytest.mark.parametrize("m", [1, 2, 5, 8, 16])
def test_batched_rebuild_is_constant_launches_per_level(m):
    """A whole-table rebuild issues O(log m) stacked launches (leaf pass
    + one per tree level up, one per complement level down, one fault
    stack), NOT O(m log m) per-merge kernel calls."""
    import math
    tasks = _tasks(m, caps=[12] * m)
    table = PlanTable(tasks, [8] * m, A800, 3600.0, 120.0,
                      engine="batched")
    depth = max(1, math.ceil(math.log2(m))) if m > 1 else 0
    assert table.batch_stats["launches"] <= 2 * depth + 1
    # eager build materializes every scenario plan via lazy traceback
    assert table.batch_stats["tracebacks"] == len(table.scenario_keys())
    if m > 1:
        assert table.batch_stats["levels"] >= 2


def test_planner_cache_prebuild_runs_value_rebuild():
    """``PlannerCache.table(prebuild=True)`` returns a table whose whole
    -table value sweep already ran (totals memoized, no tracebacks), and
    the memoized table comes back warm on a recurring state."""
    tasks = _tasks(4, caps=[10, None, 8, 12])
    assignment = [10, 10, 10, 10]
    cache = PlannerCache()
    table = cache.table(tasks, assignment, A800, 3600.0, 120.0,
                        prebuild=True)
    assert table.batch_stats["launches"] >= 1
    assert table.batch_stats["tracebacks"] == 0
    launches = table.batch_stats["launches"]
    eager = PlanTable(tasks, assignment, A800, 3600.0, 120.0)
    for key in table.scenario_keys():
        assert table.scenario_total(key) == eager.table[key].total_reward
    assert table.batch_stats["launches"] == launches   # sweep was done
    again = cache.table(tasks, assignment, A800, 3600.0, 120.0,
                        prebuild=True)                 # idempotent on hit
    assert again is table
    assert again.batch_stats["launches"] == launches


# ---- (g) fused one-program engine -----------------------------------------


@pytest.mark.parametrize("m,n,caps", [
    (1, 8, [None]), (3, 36, [10, None, 8]), (6, 96, [12] * 6),
    (7, 96, [16, None, 8, 24, None, 12, 16])])
def test_fused_engine_bitwise_identical_to_batched(m, n, caps):
    """The fused one-program engine reduces exactly the batched engine's
    candidate sets (chunked, scatter-max merged, f64 on device), so eager
    tables agree bit for bit — totals, assignments AND WAF."""
    tasks = _tasks(m, caps=caps)
    assignment = [n // m] * m
    fus = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    engine="fused")
    bat = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    engine="batched")
    assert set(fus.table) == set(bat.table)
    for key in bat.table:
        assert fus.table[key].total_reward == bat.table[key].total_reward
        assert fus.table[key].assignment == bat.table[key].assignment
        assert fus.table[key].waf == bat.table[key].waf


def test_fused_table_matches_reference():
    """Fused-engine scenario totals against the all-scalar
    ``solve_reference`` table on a capped fleet (f32 tolerance when the
    pallas backend is active — the CI leg's configuration)."""
    from repro.core.planner import get_maxplus_backend
    tol = 1e-5 if get_maxplus_backend() == "pallas" else 1e-9
    tasks = _tasks(3, caps=[10, None, 8])
    assignment = [12, 12, 12]
    fus = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    engine="fused")
    ref = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    assert set(fus.table) == set(ref.table)
    for key in ref.table:
        assert fus.table[key].total_reward == pytest.approx(
            ref.table[key].total_reward, rel=tol), key


def test_fused_whole_table_single_dispatch():
    """A whole-table rebuild on the fused engine is exactly ONE device
    dispatch — every scenario total materialized, zero tracebacks, zero
    stacked launches — and repeating it on the warm table dispatches
    nothing new.  Lookups afterwards stay host-side."""
    tasks = _tasks(5, caps=[8, None, 12, None, 6])
    assignment = [12] * 5
    cache = PlannerCache()
    lazy = cache.table(tasks, assignment, A800, 3600.0, 120.0,
                       engine="fused")
    assert lazy.batch_stats["device_dispatches"] == 0
    totals = lazy.rebuild_values()
    assert lazy.batch_stats["device_dispatches"] == 1
    assert lazy.batch_stats["launches"] == 0
    assert lazy.batch_stats["tracebacks"] == 0
    assert not lazy.table                    # values only, no Plans yet
    eager = PlanTable(tasks, assignment, A800, 3600.0, 120.0)
    assert set(totals) == set(eager.table)
    for key, total in totals.items():
        assert total == eager.table[key].total_reward, key
    lazy.rebuild_values()                    # idempotent on a warm table
    assert lazy.batch_stats["device_dispatches"] == 1
    plan = lazy.lookup("fault:2")            # traceback is host-side
    assert lazy.batch_stats["device_dispatches"] == 1
    assert lazy.batch_stats["tracebacks"] == 1
    assert plan.assignment == eager.table["fault:2"].assignment
    assert plan.total_reward == eager.table["fault:2"].total_reward


def test_fused_same_signature_churn_no_retrace():
    """Cap-constrained churn keeps the schedule signature fixed, so the
    whole walk runs ONE cached program — a single trace, one execution
    per distinct state, no program-cache growth past the first build."""
    import repro.core.planner as planner_mod
    m = 6
    tasks = _tasks(m, caps=[12] * m)
    cache = PlannerCache()
    states = [[8] * m, [8, 12, 8, 4, 8, 8], [4, 12, 8, 4, 12, 8],
              [12] * m, [4, 4, 8, 12, 8, 4]]
    sig = None
    prog = None
    dispatches = 0
    for a in states:
        table = cache.table(tasks, a, A800, 3600.0, 120.0, n_budget=80,
                            engine="fused")
        before = table.batch_stats["device_dispatches"]
        table.rebuild_values()
        dispatches += table.batch_stats["device_dispatches"] - before
        if sig is None:
            sig = table._fused_signature()
            prog = planner_mod._FUSED_PROGRAMS[sig]
        else:
            # caps bound every draw, so bands — hence the signature, and
            # with it the compiled program — never change across the walk
            assert table._fused_signature() == sig
            assert planner_mod._FUSED_PROGRAMS[sig] is prog
    assert dispatches == len(states)
    assert prog.calls >= len(states)
    # ONE trace for the whole walk
    assert prog.traces() == 1


def test_fused_engine_pallas_backend_matches_reference():
    """engine="fused" under REPRO_PLANNER_BACKEND=pallas (via the
    setter): the f32 scan-chunk kernel becomes the inner step and the
    table must match the all-scalar reference to f32 tolerance — the
    combination CI pins under REPRO_PALLAS_INTERPRET=1."""
    from repro.core.planner import set_maxplus_backend
    tasks = _tasks(2, caps=[8, None])
    ref = PlanTable(tasks, [8, 16], A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    set_maxplus_backend("pallas")
    try:
        fus = PlanTable(tasks, [8, 16], A800, 3600.0, 120.0,
                        engine="fused")
    finally:
        set_maxplus_backend(None)
    assert set(fus.table) == set(ref.table)
    for key in ref.table:
        a, b = fus.table[key], ref.table[key]
        rel = abs(a.total_reward - b.total_reward) / max(
            1.0, abs(b.total_reward))
        assert rel < 1e-5, (key, rel)
    assert fus.batch_stats["device_dispatches"] == 1


def test_batched_scenario_total_value_only():
    """``scenario_total`` never materializes assignments and agrees with
    the reference solver's totals; unknown keys return None."""
    tasks = _tasks(3, caps=[10, None, 8])
    assignment = [12, 12, 12]
    cache = PlannerCache()
    lazy = cache.table(tasks, assignment, A800, 3600.0, 120.0)
    ref = PlanTable(tasks, assignment, A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    for key in ref.table:
        got = lazy.scenario_total(key)
        assert got == pytest.approx(ref.table[key].total_reward,
                                    rel=1e-9), key
    assert lazy.scenario_total("nonsense") is None
    assert lazy.scenario_total("fault:99") is None
    assert lazy.batch_stats["tracebacks"] == 0
    assert not lazy.table
