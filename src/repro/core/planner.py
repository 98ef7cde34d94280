"""Optimal reconfiguration plan generation (§5.2).

Knapsack-style dynamic program over (tasks x workers):

    S(i, j) = max_k { S(i-1, j-k) + G(t_i, k) }           (Eq. 5)

Reward rows G(t_i, ·) are produced by each task's *objective*
(``waf.Objective`` — training WAF by default, serving goodput/SLO for
inference tasks): the planner consumes ``waf.reward`` scalars and
``waf.reward_curve`` vectors without knowing which objective built
them.  The only row property the engines rely on is the **band
contract** (see ``core.waf``): rows are flat past each task's
``max_workers`` cap.  Rows need not be monotone — the *DP value
vectors* the banded kernels take as ``prev`` are made monotone
non-decreasing at the leaves (running maxima) and stay monotone under
max-plus merging, and that is what the band proof requires.

Two solver paths share the recurrence:

* ``solve`` — the vectorized engine: reward rows come out of the
  objective's vectorized curve as whole vectors (``waf.reward_curve``),
  and the DP inner loop is a max-plus convolution evaluated as one NumPy
  windowed matrix per task (O(n^2) cells but a single vector op), with
  argmax traceback.
* ``solve_reference`` — the original pure-Python scalar DP over the
  objective's scalar ``value``, kept as the ground truth for property
  tests and the speedup baseline.

Engine registry
---------------
``engines()`` is the single discovery point for the planner's engine and
backend axes.  ``engine=`` (values from ``engines()["engine"]``:
``"batched"``/``"fused"``/``"segtree"``/``"chain"``/``"reference"``) is
the one canonical spelling, accepted by ``PlanTable``/``PlannerCache.table``
directly and as the value of the simulators'/coordinator's
``plan_engine=`` kwarg (named to coexist with ``run_monte_carlo``'s
*simulator*-axis ``engine=``).  The historical ``solver=`` /
``incremental=False`` kwargs are deprecated shims for
``engine="reference"`` and are normalized by ``resolve_engine``.

Max-plus kernel family
----------------------
The DP inner loop is a max-plus (tropical) convolution; four evaluations
share the candidate set (``prev[j-k] + g[k]``), so their maxima agree:

* ``_maxplus_vals`` — plain windowed matrix (PR-1 baseline kernel);
* ``_maxplus_vals_fast`` — row-blocked (PR-2 chain-engine kernel);
* ``_maxplus_vals_fused`` — tiled fused add+max: candidate tiles are added
  and max-reduced block-by-block so the (n x n) candidate matrix is never
  materialized, and an optional **band** restricts the convolution to
  ``k <= band``.  The band is sound whenever ``prev`` is monotone
  non-decreasing (every DP value vector is) and ``g`` is flat past the
  band (reward rows of tasks with ``Task.max_workers`` caps are; so are
  span value vectors past the sum of their tasks' caps) — the banded
  output is then bitwise-identical to the dense one.
* ``_maxplus_vals_fused_batched`` — stacked (B, n+1) variant of the
  fused kernel with a *per-row* band: one call evaluates B independent
  convolutions, each row bitwise-identical to the 2-D fused kernel on
  its own (prev, g, band) slice.  The batched engine's workhorse.
* ``kernels.maxplus.maxplus_conv`` / ``maxplus_conv_batched`` — Pallas
  TPU kernels (interpret on CPU/GPU, compiled via Mosaic on TPU),
  float32; the batched variant puts the stack axis on the Pallas grid.
  Selected with the backend switch: ``set_maxplus_backend("pallas")`` or
  ``REPRO_PLANNER_BACKEND=pallas``; default stays ``numpy`` (float64).
* ``kernels.maxplus.maxplus_scan_chunk`` — the scan-compatible Pallas
  chunk step the fused engine runs inside its one-program ``lax.scan``
  when the pallas backend is selected (pre-gathered static-width
  operands, so one trace serves every scan step).

Incremental engine matrix (chain -> segtree -> batched -> fused)
----------------------------------------------------------------
``PlanTable`` precomputes the one-step lookahead lookup table the paper
uses for O(1) dispatch at failure time.  Four incremental engines build
it (mirroring the scalar -> vector -> batched simulator matrix):

* ``engine="chain"`` — the PR-2 prefix/suffix DP chains: P[i]/T[i] value
  vectors, each scenario assembled from <= 2 extra convolutions, a churn
  step invalidates the O(m) chain tail past the change.  Kept unchanged
  as the measured churn-rebuild baseline (``bench_planner_scale``).
* ``engine="segtree"`` — a dyadic segment tree over task positions
  (PR 3).  Each node stores the max-plus merge V[lo, hi) of its span's
  reward rows (leaves are running maxima, internal nodes one banded
  convolution of their children), and every scenario assembles from
  O(log m) cached node merges: ``join`` reads the root, ``finish:i`` the
  complement chain C(i) = merge of i's root-path siblings, ``fault:i``
  one extra banded convolution of C(i) with the fault row.  A churn step
  that changes one task's reward row invalidates only the O(log m) nodes
  on its root path (plus the complements crossing it) — but every node
  merge and every chain link is still its own Python-dispatched kernel
  call, and every ``lookup`` pays an O(m) argmax traceback.
* ``engine="batched"`` (default) — the level-synchronous batched engine
  on the same dyadic tree, three upgrades over ``segtree``:

  1. *Level-stacked merges*: tree nodes are grouped by depth and each
     level's merges run as ONE stacked banded max-plus call
     (``_maxplus_vals_fused_batched``), so a whole-tree build is
     O(log m) kernel launches instead of O(m) Python-driven calls.
  2. *Shared complement sweep*: the m ``fault:i``/``finish:i``
     complement chains overlap in O(m) distinct nodes — one top-down
     level-parallel sweep computes the complement vector of EVERY tree
     node (Comp(child) = Comp(parent) (+) V(sibling), all children of a
     level in one stacked call), then all m fault combines run as one
     more stacked call.  A whole-table value rebuild is therefore a
     constant number of batched launches per tree level.
  3. *Value-only assembly + lazy traceback*: ``rebuild_values()`` /
     ``scenario_total()`` materialize every scenario's value vector and
     total reward but NO assignments; the O(m) argmax traceback runs
     only for the scenario a ``lookup`` actually dispatches.

* ``engine="fused"`` — the one-program engine: the ENTIRE whole-table
  value rebuild (level-synchronous tree merges, top-down complement
  sweep, per-task fault combines, per-scenario argmaxes and totals) is
  ONE jitted device dispatch.  A host-side *schedule builder* decomposes
  every banded convolution of the batched engine's sweep — same
  operands, operand orders and bands — into fixed-width candidate-offset
  chunks that scatter-max into a slot buffer, groups the chunk rows by
  dependency level, and the compiled program runs ``lax.scan`` over the
  resulting step table with either a pure-``jnp`` float64 inner step
  (default; bitwise-identical totals to the numpy engines) or the Pallas
  ``maxplus_scan_chunk`` kernel under ``REPRO_PLANNER_BACKEND=pallas``.

  *Schedule padding contract*: every level's chunk rows are padded to a
  multiple of the scan group width with -inf dummy rows (band = -1
  masks the whole chunk, and a -inf row scatter-maxes to a no-op), and
  per-row ragged bands are masked to -inf inside the step — padding is
  value-neutral because a masked candidate never beats the always-finite
  k=0 candidate.  *Retrace keys*: compiled programs are cached per
  schedule signature (m, n_max, per-task unfaulted/faulted bands,
  backend) — reward-row *values* are runtime inputs, so churn that
  preserves caps and budgets re-dispatches the cached program with zero
  retraces; a capacity or cap change is a new signature (new trace), not
  an error.  ``batch_stats["device_dispatches"]`` counts exactly 1 per
  whole-table rebuild.  Lazy single-scenario lookups before a rebuild,
  and every argmax traceback, stay on the host-side batched machinery
  unchanged; node vectors are not written to the ``PlannerCache`` array
  store (the program cache replaces content-keyed reuse on this path).

  All four engines reduce identical candidate sets with exact
  order-free maxima, so their plans are float-identical.

With ``lazy=True`` scenarios (and the node merges feeding them) are
assembled on first ``lookup``; with a ``PlannerCache`` reward rows and
node/chain vectors are keyed by their span *contents* and reused across
rebuilds, and a recurring cluster state is a whole-table hit.  The
churn-heavy cluster simulators (``core.simulator.VectorSimulator`` /
``BatchSimulator``) are the main consumers; their cold Monte-Carlo walls
are planner-dispatch-bound, which is what the batched engine's
constant-launch rebuilds attack (``bench_planner_scale``'s whole-table
churn axis measures it directly).

``brute_force`` is an exponential reference used by the property tests.
Regenerate the committed benchmark baselines (``results/bench_*.json``)
with ``python benchmarks/run.py`` after any reward-model change here
(``python benchmarks/run.py --only planner_scale`` re-records a single
bench after a planner-only change).
"""
from __future__ import annotations

import itertools
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core import waf as waf_mod
from repro.core.costmodel import Hardware
from repro.core.waf import Task

NEG = float("-inf")


@dataclass(frozen=True)
class PlanInput:
    tasks: Tuple[Task, ...]
    assignment: Tuple[int, ...]        # current workers per task (x_i)
    n_workers: int                     # n' available after the event
    d_running: float
    d_transition: float
    faulted: Tuple[bool, ...]          # per task: did one of its workers fault


@dataclass(frozen=True)
class Plan:
    assignment: Tuple[int, ...]
    total_reward: float
    waf: float                         # cluster WAF under the new assignment


def _vector_capable(tasks: Sequence) -> bool:
    """Reward rows can be built from the objective's vectorized curve
    (real ``Task``s whose objective declares itself vector-capable — the
    default ``TrainingWAF`` requires an analytic ``TaskModel``).
    Duck-typed tasks — e.g. the tabulated tasks the property tests use
    with a monkeypatched ``waf`` — fall back to the scalar row builder
    so they keep their custom semantics."""
    return all(isinstance(t, Task) and t.objective.vector_capable(t)
               for t in tasks)


def _reward_row(inp: PlanInput, i: int, hw: Hardware) -> List[float]:
    """G(t_i, k) for k = 0..n_workers (scalar reference path)."""
    t = inp.tasks[i]
    return [waf_mod.reward(t, inp.assignment[i], k,
                           d_running=inp.d_running,
                           d_transition=inp.d_transition,
                           worker_faulted=inp.faulted[i], hw=hw)
            for k in range(inp.n_workers + 1)]


def _reward_matrix(inp: PlanInput, hw: Hardware) -> np.ndarray:
    """All m reward rows as an (m, n+1) matrix."""
    if _vector_capable(inp.tasks):
        return np.stack([
            waf_mod.reward_curve(t, inp.assignment[i], inp.n_workers,
                                 d_running=inp.d_running,
                                 d_transition=inp.d_transition,
                                 worker_faulted=inp.faulted[i], hw=hw)
            for i, t in enumerate(inp.tasks)])
    return np.array([_reward_row(inp, i, hw)
                     for i in range(len(inp.tasks))], dtype=float)


def _maxplus(prev: np.ndarray, g: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """One max-plus convolution step: out[j] = max_{0<=k<=j} prev[j-k] + g[k],
    plus the argmax k per j (first/lowest k on ties, matching the scalar
    DP's strict-improvement rule)."""
    n = prev.shape[0] - 1
    pad = np.concatenate([np.full(n, NEG), prev])
    win = np.lib.stride_tricks.sliding_window_view(pad, n + 1)
    vals = win[:, ::-1] + g[None, :]   # vals[j, k] = prev[j-k] + g[k]
    ch = vals.argmax(axis=1)           # one O(n^2) scan serves both outputs
    return vals[np.arange(n + 1), ch], ch


def _maxplus_vals(prev: np.ndarray, g: np.ndarray) -> np.ndarray:
    """Value vector of one max-plus step, without the per-cell argmax.

    Same candidate set per cell as ``_maxplus`` (so the maxima are
    float-identical), but evaluated without reversing the O(n^2) window
    matrix; tracebacks recover choices per *visited* cell via
    ``_argmax_at`` instead of materializing the whole argmax matrix."""
    n = prev.shape[0] - 1
    pad = np.concatenate([np.full(n, NEG), prev])
    win = np.lib.stride_tricks.sliding_window_view(pad, n + 1)
    return (win + g[::-1][None, :]).max(axis=1)


def _maxplus_vals_fast(prev: np.ndarray, g: np.ndarray) -> np.ndarray:
    """Bitwise-identical values to ``_maxplus_vals``, evaluated in row
    blocks that skip most of the -inf padding triangle (cell j only has
    j+1 real candidates; the rectangular kernel evaluates all n+1).
    Every real candidate is the same ``prev[j-k] + g[k]`` float and max
    is an exact, order-free reduction, so the output is unchanged.  This
    is the kernel of the cached/lazy engine path; the eager reference
    build keeps the plain kernels as the measured baseline."""
    n = prev.shape[0] - 1
    pad = np.concatenate([np.full(n, NEG), prev])
    win = np.lib.stride_tricks.sliding_window_view(pad, n + 1)
    gr = g[::-1]
    out = np.empty(n + 1)
    block = 128
    for j0 in range(0, n + 1, block):
        j1 = min(j0 + block, n + 1)
        t_lo = n - j1 + 1          # rows below j1 have no candidate before
        out[j0:j1] = (win[j0:j1, t_lo:] + gr[t_lo:]).max(axis=1)
    return out


def _maxplus_vals_fused(prev: np.ndarray, g: np.ndarray,
                        band: Optional[int] = None,
                        block: Optional[int] = None) -> np.ndarray:
    """Tiled fused add+max max-plus convolution.

    out[j] = max_{0 <= k <= min(j, band)} prev[j-k] + g[k]

    Candidate tiles of at most (block, band+1) cells are added and
    max-reduced immediately, so peak scratch is one tile — the (n x n)
    candidate matrix of the plain kernels is never materialized.  With
    ``band=None`` (dense) the candidate set per cell is exactly
    ``_maxplus_vals``'s, so the output is bitwise identical.  A finite
    band is sound — and still bitwise identical to dense — when ``prev``
    is monotone non-decreasing and ``g`` is flat past the band: every
    dropped candidate ``prev[j-k] + g[k]`` (k > band) is dominated by
    ``prev[j-band] + g[band]``, and first-max tie-breaking already picks
    the lowest k.

    Tile orientation adapts to the band: a narrow band (<= 1/4 of the
    width) lays k along the short outer axis and j along the long
    contiguous axis, so numpy's per-row loop overhead scales with the
    band instead of with n; wide/dense bands keep the j-blocked layout
    whose tiles bound peak scratch at one (block, band+1) slab.  Both
    orientations max-reduce the same candidate floats, so tiling never
    changes values."""
    n = prev.shape[0] - 1
    b = n if band is None else max(0, min(int(band), n))
    pad = np.concatenate([np.full(b, NEG), prev])
    if 4 * (b + 1) <= n + 1:           # narrow band: k-major tiles
        winT = np.lib.stride_tricks.sliding_window_view(pad, n + 1)
        gr = g[b::-1][:, None]         # gr[t] = g[b - t], i.e. k = b - t
        width = max(128, 131072 // (b + 1)) if block is None else block
        out = np.empty(n + 1)
        for j0 in range(0, n + 1, width):
            j1 = min(j0 + width, n + 1)
            out[j0:j1] = (winT[:, j0:j1] + gr).max(axis=0)
        return out
    if block is None:
        block = 128
    win = np.lib.stride_tricks.sliding_window_view(pad, b + 1)
    gr = g[b::-1]
    out = np.empty(n + 1)
    for j0 in range(0, n + 1, block):
        j1 = min(j0 + block, n + 1)
        t_lo = max(b - j1 + 1, 0)      # rows below j1 have no candidate before
        out[j0:j1] = (win[j0:j1, t_lo:] + gr[t_lo:]).max(axis=1)
    return out


def _maxplus_kloop_stack(prev: np.ndarray, g: np.ndarray,
                         bs: np.ndarray) -> np.ndarray:
    """Shift-slab evaluation of a stacked banded convolution: one
    iteration per candidate offset k, each a fused add + in-place max
    over the whole contiguous (B, n+1) slab.

    out[r, j] = max_{0 <= k <= min(j, bs[r])} prev[r, j-k] + g[r, k]

    Per-row bands are applied by masking g past each row's band to -inf
    (a masked candidate never beats the finite k=0 candidate); k > j
    candidates fall into the -inf pad.  Max is an exact order-free
    reduction over the same ``prev[r, j-k] + g[r, k]`` floats as the 2-D
    fused kernel, so rows are bitwise identical to per-slice calls."""
    B, n1 = prev.shape
    bmax = int(bs.max())
    pad = np.concatenate([np.full((B, bmax), NEG), prev], axis=1)
    gm = g
    if (bs < bmax).any():
        gm = np.where(np.arange(n1)[None, :] > bs[:, None], NEG, g)
    out = np.full((B, n1), NEG)
    tmp = np.empty((B, n1))
    for k in range(bmax + 1):
        np.add(pad[:, bmax - k: bmax - k + n1], gm[:, k:k + 1], out=tmp)
        np.maximum(out, tmp, out=out)
    return out


def _maxplus_vals_fused_batched(prev: np.ndarray, g: np.ndarray,
                                bands=None) -> np.ndarray:
    """Stacked banded max-plus convolution: B independent rows at once.

    ``prev`` and ``g`` are (B, n+1); ``bands`` is a per-row band sequence
    (``None`` entries = dense).  Row r of the output is **bitwise
    identical** to ``_maxplus_vals_fused(prev[r], g[r], band=bands[r])``:
    every path below reduces exactly row r's candidate set with exact
    order-free maxima.

    One call replaces a Python loop of B 2-D kernel calls — the
    per-level launch of the ``engine="batched"`` PlanTable.  Like the
    2-D kernel's orientation adaptivity, the evaluation strategy follows
    the shape: rows are bucketed by band (each bucket spans at most a 2x
    band spread, bounding masked-candidate waste), narrow buckets run as
    shift-slab stacks whose Python-loop count is the band instead of the
    batch (``_maxplus_kloop_stack``), and wide/dense buckets — where one
    row's candidate matrix already saturates the memory system and
    stacking only thrashes it — fall through to the tiled 2-D kernel per
    row."""
    prev = np.asarray(prev, dtype=float)
    g = np.asarray(g, dtype=float)
    B, n1 = prev.shape
    n = n1 - 1
    if bands is None:
        bs = np.full(B, n, dtype=np.int64)
    else:
        bs = np.array([n if b is None else max(0, min(int(b), n))
                       for b in bands], dtype=np.int64)
    out = np.empty((B, n1))
    order = np.argsort(bs, kind="stable")
    start = 0
    while start < B:
        stop = start + 1
        floor = bs[order[start]]
        while (stop < B
               and bs[order[stop]] + 1 <= 2 * (floor + 1)):
            stop += 1
        rows = order[start:stop]
        bmax = int(bs[rows[-1]])
        if bmax + 1 <= 4 * len(rows):      # narrow bucket: slab stack
            out[rows] = _maxplus_kloop_stack(prev[rows], g[rows],
                                             bs[rows])
        else:                              # wide/dense: per-row tiles
            for r in rows:
                out[r] = _maxplus_vals_fused(prev[r], g[r],
                                             band=int(bs[r]))
        start = stop
    return out


# ---------------------------------------------------------------------------
# Max-plus backend switch: numpy (float64, default) or the Pallas kernel
# (kernels.maxplus.maxplus_conv, float32; interpret off-TPU).
# ---------------------------------------------------------------------------

_BACKEND_ENV = "REPRO_PLANNER_BACKEND"
_BACKENDS = ("numpy", "pallas")
_backend_override: Optional[str] = None


def set_maxplus_backend(name: Optional[str]) -> None:
    """Select the max-plus convolution backend for the incremental engines:
    ``"numpy"`` / ``"pallas"``, or ``None`` to defer to the
    ``REPRO_PLANNER_BACKEND`` env var (default numpy)."""
    global _backend_override
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"unknown max-plus backend {name!r}; "
                         f"choose from {_BACKENDS}")
    _backend_override = name


def get_maxplus_backend() -> str:
    if _backend_override is not None:
        return _backend_override
    env = os.environ.get(_BACKEND_ENV, "").strip().lower()
    if env and env not in _BACKENDS:
        raise ValueError(f"{_BACKEND_ENV}={env!r} is not recognized; "
                         f"choose from {_BACKENDS}")
    return env or "numpy"


# ---------------------------------------------------------------------------
# Engine registry: the single discovery point for the planner's engine and
# backend axes (see the module docstring's "Engine registry" section).
# ---------------------------------------------------------------------------

ENGINES = ("batched", "fused", "segtree", "chain", "reference")

_ENGINE_DESCRIPTIONS = {
    "batched": "level-synchronous stacked dyadic tree; value-only "
               "rebuilds + lazy traceback (default)",
    "fused": "one-program engine: whole-table value rebuild compiled "
             "into a single jitted lax.scan dispatch (program cache "
             "keyed on the schedule signature)",
    "segtree": "per-node dyadic segment tree, O(log m) churn "
               "invalidation, one kernel call per merge",
    "chain": "prefix/suffix DP chains; the preserved churn-rebuild "
             "baseline",
    "reference": "non-incremental per-scenario solves (scalar "
                 "solve_reference by default); the ground-truth path",
}

_BACKEND_DESCRIPTIONS = {
    "numpy": "float64 fused numpy kernels (default)",
    "pallas": "float32 Pallas TPU kernels (interpret off-TPU); "
              "set_maxplus_backend('pallas') or "
              "REPRO_PLANNER_BACKEND=pallas",
}


def engines() -> Dict[str, Dict[str, str]]:
    """The planner's engine/backend registry.

    Returns ``{"engine": {name: description}, "backend": {...}}``.  The
    ``engine`` axis is spelled ``engine=`` on ``PlanTable`` /
    ``PlannerCache.table`` and ``plan_engine=`` on the simulators and
    ``UnicronCoordinator`` (same values; the kwarg differs only because
    ``run_monte_carlo``'s ``engine=`` already names the simulator axis).
    The ``backend`` axis is the process-wide max-plus kernel switch
    (``set_maxplus_backend`` / ``REPRO_PLANNER_BACKEND``)."""
    return {"engine": dict(_ENGINE_DESCRIPTIONS),
            "backend": dict(_BACKEND_DESCRIPTIONS)}


def resolve_engine(engine: Optional[str] = None, *,
                   solver=None, incremental: bool = True,
                   default: str = "batched") -> str:
    """Normalize the historical spellings of the engine axis to one
    canonical name from ``engines()["engine"]``.

    ``solver=`` (any non-None per-scenario solver) and
    ``incremental=False`` are deprecated shims for
    ``engine="reference"``; an explicit ``engine=`` name passes through
    unchanged otherwise.  Unknown names raise ``ValueError``."""
    if engine is not None and engine not in ENGINES:
        raise ValueError(f"unknown PlanTable engine {engine!r}; "
                         f"choose from {ENGINES}")
    if solver is not None or not incremental:
        return "reference"
    return engine if engine is not None else default


def _conv_vals(prev: np.ndarray, g: np.ndarray,
               band: Optional[int] = None) -> np.ndarray:
    """Backend-dispatched banded max-plus value kernel (segment-tree
    engine's convolution).  Traceback-time argmax recovery stays on
    numpy either way — only the value vectors go through the kernel."""
    if get_maxplus_backend() == "pallas":
        from repro.kernels.maxplus import maxplus_conv
        return np.asarray(maxplus_conv(prev, g, band=band), dtype=float)
    return _maxplus_vals_fused(prev, g, band)


def _conv_vals_batched(prev: np.ndarray, g: np.ndarray,
                       bands) -> np.ndarray:
    """Backend-dispatched stacked banded max-plus kernel (the batched
    engine's per-level launch): numpy float64 by default, the
    grid-batched Pallas kernel (float32) under the same
    ``REPRO_PLANNER_BACKEND=pallas`` switch as the 2-D path."""
    if get_maxplus_backend() == "pallas":
        from repro.kernels.maxplus import maxplus_conv_batched
        return np.asarray(maxplus_conv_batched(prev, g, bands), dtype=float)
    return _maxplus_vals_fused_batched(prev, g, bands)


def _argmax_at(prev: np.ndarray, g: np.ndarray, j: int) -> int:
    """Choice k at cell j of ``_maxplus(prev, g)``: first/lowest k on ties
    (all candidates with k > j are -inf, so restricting to k <= j is
    exactly the stored-argmax matrix's answer)."""
    return int(np.argmax(prev[j::-1] + g[:j + 1]))


def _cluster_waf(tasks: Sequence[Task], assign: Sequence[int],
                 hw: Hardware) -> float:
    return sum(waf_mod.waf(t, x, hw) for t, x in zip(tasks, assign))


def solve(inp: PlanInput, hw: Hardware) -> Plan:
    """Vectorized dynamic program (Eq. 5) with traceback."""
    m, n = len(inp.tasks), inp.n_workers
    if m == 0:
        return Plan((), 0.0, 0.0)
    rows = _reward_matrix(inp, hw)
    S = np.zeros(n + 1)
    choice = np.zeros((m, n + 1), dtype=np.int64)
    for i in range(m):
        S, choice[i] = _maxplus(S, rows[i])
    assign = [0] * m
    j = int(np.argmax(S))
    total = float(S[j])
    for i in range(m - 1, -1, -1):
        k = int(choice[i, j])
        assign[i] = k
        j -= k
    return Plan(tuple(assign), total, _cluster_waf(inp.tasks, assign, hw))


def solve_fast(inp: PlanInput, hw: Hardware) -> Plan:
    """Same Plan as ``solve`` (same candidate floats, same first-max
    tie-breaking) using the value-only row-blocked kernel and
    traceback-time argmax recovery instead of per-cell argmax matrices —
    the fresh-dispatch path of the cached engine."""
    m, n = len(inp.tasks), inp.n_workers
    if m == 0:
        return Plan((), 0.0, 0.0)
    rows = _reward_matrix(inp, hw)
    S = [np.zeros(n + 1)]
    for i in range(m):
        S.append(_maxplus_vals_fast(S[i], rows[i]))
    assign = [0] * m
    j = int(np.argmax(S[m]))
    total = float(S[m][j])
    for i in range(m - 1, -1, -1):
        k = _argmax_at(S[i], rows[i], j)
        assign[i] = k
        j -= k
    return Plan(tuple(assign), total, _cluster_waf(inp.tasks, assign, hw))


def solve_reference(inp: PlanInput, hw: Hardware) -> Plan:
    """Scalar reference DP (the original implementation): property-test
    ground truth and the speedup baseline for the benchmarks."""
    m, n = len(inp.tasks), inp.n_workers
    rows = [_reward_row(inp, i, hw) for i in range(m)]
    # S[i][j]: best reward of first i tasks using j workers
    S = [[0.0] + [0.0] * n]
    choice: List[List[int]] = []
    for i in range(1, m + 1):
        row = [NEG] * (n + 1)
        ch = [0] * (n + 1)
        g = rows[i - 1]
        for j in range(n + 1):
            best, bk = NEG, 0
            for k in range(j + 1):
                v = S[i - 1][j - k] + g[k]
                if v > best:
                    best, bk = v, k
            row[j], ch[j] = best, bk
        S.append(row)
        choice.append(ch)
    # traceback from S(m, n)
    assign = [0] * m
    j = max(range(n + 1), key=lambda jj: S[m][jj])
    total = S[m][j]
    for i in range(m, 0, -1):
        k = choice[i - 1][j]
        assign[i - 1] = k
        j -= k
    return Plan(tuple(assign), total, _cluster_waf(inp.tasks, assign, hw))


def brute_force(inp: PlanInput, hw: Hardware) -> Plan:
    """Exponential reference solver (tests only)."""
    m, n = len(inp.tasks), inp.n_workers
    rows = [_reward_row(inp, i, hw) for i in range(m)]
    best: Optional[Tuple[float, Tuple[int, ...]]] = None
    for assign in itertools.product(range(n + 1), repeat=m):
        if sum(assign) > n:
            continue
        v = sum(rows[i][assign[i]] for i in range(m))
        if best is None or v > best[0]:
            best = (v, assign)
    v, assign = best
    return Plan(tuple(assign), v, _cluster_waf(inp.tasks, assign, hw))


# ---------------------------------------------------------------------------
# Fused one-program engine: schedule builder + compiled program cache.
#
# The whole-table value rebuild of the batched engine — level-synchronous
# tree merges, top-down complement sweep, fault combines, scenario argmaxes
# — becomes ONE jitted device dispatch.  See the module docstring's
# ``engine="fused"`` entry for the padding contract and retrace keys.
# ---------------------------------------------------------------------------

_FUSED_GROUP = 32   # scan step width G: chunk rows per lax.scan step
_FUSED_ROW_COST = 4  # per-chunk-row overhead (gather/mask/scatter), in
#                      units of n1 cells — the adaptive-K cost model's
#                      only tunable


def _fused_chunk_width(bands: Sequence[int]) -> int:
    """Adaptive candidate-offset chunk width K for one schedule: minimize
    padded candidate slots + per-row overhead over the signature's actual
    band distribution.  K is static per compiled program (it sets every
    ``dynamic_slice`` width), so this is trace-time work — e.g. a fleet
    of cap-16 tasks picks K=17 (band-16 ops become one exact chunk)
    instead of padding every 17-candidate op to a power of two."""
    if not bands:
        return 16
    best_k, best_cost = 16, None
    for k in range(8, 65):
        cost = sum(-(-(b + 1) // k) * (k + _FUSED_ROW_COST)
                   for b in bands)
        if best_cost is None or cost < best_cost:
            best_k, best_cost = k, cost
    return best_k


class _FusedSchedule:
    """Static whole-table rebuild schedule for one signature
    (m, n_max, per-task bands).

    Every banded max-plus convolution of the batched sweep is decomposed
    into ``ceil((band+1)/K)`` chunk rows — chunk ``c`` covering candidate
    offsets ``[cK, cK+K)`` — which scatter-max into the op's output slot
    (exact: the candidate set partitions over offset chunks and max is
    order-free).  Chunk rows are grouped by dependency level (merges
    bottom-up by tree depth, then the complement sweep top-down, then the
    fault combines), each level padded to a multiple of the group width
    ``G`` with inert dummy rows (band = -1), and flattened into
    ``(steps, G)`` int32 step tables a single ``lax.scan`` consumes.

    All vectors live in one (n_slots, width) slot buffer with ``K``-aware
    -inf margins on both sides, so a chunk's shifted ``prev`` window and
    its ``g`` chunk are plain ``dynamic_slice`` gathers at trace-friendly
    static widths.  Operand orders and bands mirror ``_build_spans`` /
    ``_ensure_values`` exactly — outputs are bitwise-identical."""

    def __init__(self, m: int, n_max: int,
                 bands_unf: Tuple[int, ...], bands_f: Tuple[int, ...],
                 chunk: Optional[int] = None, group: int = _FUSED_GROUP):
        self.m, self.n_max = m, n_max
        self.group = group
        self.n1 = n_max + 1

        levels: List[List[Tuple[int, int]]] = []

        def walk(lo: int, hi: int, d: int) -> None:
            if len(levels) <= d:
                levels.append([])
            levels[d].append((lo, hi))
            if hi - lo > 1:
                mid = (lo + hi) // 2
                walk(lo, mid, d + 1)
                walk(mid, hi, d + 1)

        walk(0, m, 0)
        self.levels = levels
        nodes = [nd for lvl in levels for nd in lvl]
        self.v_slot = {nd: i for i, nd in enumerate(nodes)}
        base = len(nodes)
        self.c_slot = {nd: base + i for i, nd in enumerate(nodes)}
        base += len(nodes)
        self.fault_slot = {i: base + i for i in range(m)}
        base += m
        self.frow_slot = {i: base + i for i in range(m)}
        base += m
        self.scratch = base
        self.n_slots = base + 1

        sat_memo: Dict[Tuple[int, int], int] = {}

        def sat(lo: int, hi: int) -> int:
            got = sat_memo.get((lo, hi))
            if got is None:
                got = min(sum(bands_unf[lo:hi]), n_max)
                sat_memo[(lo, hi)] = got
            return got

        # op_steps: dependency-ordered groups of (prev, g, band, out).
        op_steps: List[List[Tuple[int, int, int, int]]] = []
        # V up-sweep: internal merges bottom-up, one step group per tree
        # depth (children are strictly deeper -> already reduced).
        for d in reversed(range(len(levels))):
            ops: List[Tuple[int, int, int, int]] = []
            for lo, hi in levels[d]:
                if hi - lo == 1:
                    continue
                mid = (lo + hi) // 2
                sl, sr = sat(lo, mid), sat(mid, hi)
                if sl < sr:               # band by the flatter operand
                    prev, g, band = (mid, hi), (lo, mid), sl
                else:
                    prev, g, band = (lo, mid), (mid, hi), sr
                ops.append((self.v_slot[prev], self.v_slot[g],
                            min(band, n_max), self.v_slot[(lo, hi)]))
            if ops:
                op_steps.append(ops)
        # Complement down-sweep: Comp(child) = Comp(parent) (+) V(sib).
        csat: Dict[Tuple[int, int], int] = {(0, m): 0}
        for d in range(len(levels) - 1):
            ops = []
            for lo, hi in levels[d]:
                if hi - lo == 1:
                    continue
                mid = (lo + hi) // 2
                for child, sib in (((lo, mid), (mid, hi)),
                                   ((mid, hi), (lo, mid))):
                    satc, sat_v = csat[(lo, hi)], sat(*sib)
                    csat[child] = min(satc + sat_v, n_max)
                    if satc < sat_v:      # band by the flatter operand
                        prev, g, band = (self.v_slot[sib],
                                         self.c_slot[(lo, hi)], satc)
                    else:
                        prev, g, band = (self.c_slot[(lo, hi)],
                                         self.v_slot[sib], sat_v)
                    ops.append((prev, g, min(band, n_max),
                                self.c_slot[child]))
            if ops:
                op_steps.append(ops)
        # Fault combines: Comp(leaf i) (+) faulted row i.
        ops = [(self.c_slot[(i, i + 1)], self.frow_slot[i],
                min(bands_f[i], n_max), self.fault_slot[i])
               for i in range(m)]
        if ops:
            op_steps.append(ops)

        # Static per-signature traceback metadata, bulk-copied into the
        # table's stores after a dispatch (saves the per-rebuild python
        # sweep the batched engine pays): span saturations, comp-tree
        # cumulative saturations and sibling paths.
        self.sat_map = dict(sat_memo)
        self.csat_map = csat
        csibs: Dict[Tuple[int, int], Tuple] = {(0, m): ()}
        for d in range(len(levels) - 1):
            for lo, hi in levels[d]:
                if hi - lo == 1:
                    continue
                mid = (lo + hi) // 2
                for child, sib in (((lo, mid), (mid, hi)),
                                   ((mid, hi), (lo, mid))):
                    csibs[child] = csibs[(lo, hi)] + (sib,)
        self.csibs_map = csibs

        all_bands = [op[2] for ops in op_steps for op in ops]
        self.chunk = chunk = (_fused_chunk_width(all_bands)
                              if chunk is None else chunk)
        steps: List[List[Tuple[int, int, int, int, int]]] = []
        for ops in op_steps:
            rows = [(prev, g, c, band, out)
                    for prev, g, band, out in ops
                    for c in range(0, band + 1, chunk)]
            steps.append(rows)
        # left margin sized to the widest chunk offset actually scheduled
        # (window start padl - off - (K-1) stays > 0, so dynamic_slice
        # never clamps); right margin keeps g-chunk reads past n_max in
        # -inf territory.  The scan carries the whole buffer, so every
        # saved column is saved once per step.
        max_off = max((r[2] for rows in steps for r in rows), default=0)
        self.padl = max_off + chunk
        self.width = self.padl + self.n1 + chunk

        dummy = (self.scratch, self.scratch, 0, -1, self.scratch)
        packed: List[Tuple[int, int, int, int, int]] = []
        self.real_rows = 0
        for rows in steps:
            self.real_rows += len(rows)
            rows = rows + [dummy] * (-len(rows) % group)
            packed.extend(rows)
        if not packed:
            packed = [dummy] * group
        table = np.asarray(packed, dtype=np.int32).reshape(-1, group, 5)
        self.n_steps = table.shape[0]
        self.xs = tuple(np.ascontiguousarray(table[:, :, i])
                        for i in range(5))
        self.leaf_slots = np.asarray(
            [self.v_slot[(i, i + 1)] for i in range(m)], dtype=np.int32)
        self.frow_slots = np.asarray(
            [self.frow_slot[i] for i in range(m)], dtype=np.int32)
        self.root_c_slot = self.c_slot[(0, m)]
        # scenario readout order: fault:0..m-1, finish:0..m-1, join:1
        self.scen_slots = np.asarray(
            [self.fault_slot[i] for i in range(m)]
            + [self.c_slot[(i, i + 1)] for i in range(m)]
            + [self.v_slot[(0, m)]], dtype=np.int32)


class _FusedProgram:
    """One compiled whole-table rebuild for a schedule signature.

    ``__call__(g_unf, g_f, limits)`` runs the single jitted dispatch:
    reward-row stacks (m, n+1) float64 and the (2m+1,) per-scenario
    argmax limits are the only runtime inputs; the step tables are
    trace-time constants.  Returns host arrays: the (n_slots, n+1) slot
    values, per-scenario argmax cells, and totals.  Traced and invoked
    under ``jax.enable_x64(True)`` so the default backend stays
    float64 — on a backend with IEEE float64 (the CPU) totals are then
    bitwise-identical to the numpy engines (each candidate is a single
    IEEE add; max is order-free).  A TPU v5e emulates float64 with pairs
    of float32, and there totals agree to ~4e-15 relative.  Under the
    pallas backend the inner step is ``maxplus_scan_chunk`` (float32
    kernel arithmetic, float64 buffer), matching the batched engine's
    pallas precision exactly."""

    def __init__(self, sched: _FusedSchedule, backend: str):
        import jax                        # deferred: numpy engines never
        self._jax = jax                   # pay the jax import
        self.sched = sched
        self.backend = backend
        self.calls = 0
        self._fn = jax.jit(self._program)

    def traces(self) -> int:
        """Compiled-trace count of the jitted program (the no-retrace
        assertion probe)."""
        return int(self._fn._cache_size())

    def _program(self, g_unf, g_f, limits):
        jax = self._jax
        jnp = jax.numpy
        sc = self.sched
        dt = g_unf.dtype
        K, n1, padl = sc.chunk, sc.n1, sc.padl
        buf = jnp.full((sc.n_slots, sc.width), NEG, dt)
        leaves = jax.lax.cummax(g_unf, axis=1)     # running maxima
        buf = buf.at[sc.leaf_slots, padl:padl + n1].set(leaves)
        buf = buf.at[sc.frow_slots, padl:padl + n1].set(g_f)
        buf = buf.at[sc.root_c_slot, padl:padl + n1].set(
            jnp.zeros((n1,), dt))

        def step(b, xs):
            src, gsl, off, band, out = xs
            wins = jax.vmap(
                lambda r, o: jax.lax.dynamic_slice(
                    r, (padl - o - (K - 1),), (n1 + K - 1,))
            )(b[src], off)
            gs = jax.vmap(
                lambda r, o: jax.lax.dynamic_slice(r, (padl + o,), (K,))
            )(b[gsl], off)
            ks = off[:, None] + jnp.arange(K, dtype=off.dtype)[None, :]
            gs = jnp.where(ks <= band[:, None], gs, NEG)
            if self.backend == "pallas":
                from repro.kernels.maxplus import maxplus_scan_chunk
                acc = maxplus_scan_chunk(wins, gs).astype(dt)
            else:
                acc = jnp.full((wins.shape[0], n1), NEG, dt)
                for k in range(K):        # static unroll: fused add+max
                    acc = jnp.maximum(
                        acc, wins[:, K - 1 - k:K - 1 - k + n1]
                        + gs[:, k:k + 1])
            return b.at[out, padl:padl + n1].max(acc), None

        buf, _ = jax.lax.scan(step, buf, sc.xs)
        vals = buf[:, padl:padl + n1]
        scen = vals[sc.scen_slots]
        mask = jnp.arange(n1)[None, :] <= limits[:, None]
        js = jnp.argmax(jnp.where(mask, scen, NEG), axis=1)
        totals = jnp.take_along_axis(scen, js[:, None], axis=1)[:, 0]
        return vals, js, totals

    def __call__(self, g_unf: np.ndarray, g_f: np.ndarray,
                 limits: np.ndarray):
        with self._jax.enable_x64(True):  # trace AND dispatch in f64
            with obs.span("plan.program.wait"):
                got = self._jax.block_until_ready(
                    self._fn(g_unf, g_f, limits))
            with obs.span("plan.fetch"):
                out = tuple(np.asarray(x) for x in got)
        self.calls += 1
        return out


_FUSED_PROGRAMS: OrderedDict = OrderedDict()
_FUSED_PROGRAM_CAP = 32
_fused_lock = threading.Lock()


def _fused_program(m: int, n_max: int, bands_unf: Tuple[int, ...],
                   bands_f: Tuple[int, ...], backend: str) -> _FusedProgram:
    """Process-wide LRU of compiled fused programs, keyed on the schedule
    signature — same-signature churn rebuilds re-dispatch without
    retracing (reward values are runtime inputs)."""
    key = (m, n_max, bands_unf, bands_f, backend)
    with _fused_lock:
        prog = _FUSED_PROGRAMS.get(key)
        if prog is not None:
            _FUSED_PROGRAMS.move_to_end(key)
            return prog
    with obs.span("plan.schedule", m=m):
        sched = _FusedSchedule(m, n_max, bands_unf, bands_f)
    prog = _FusedProgram(sched, backend)
    with _fused_lock:
        got = _FUSED_PROGRAMS.setdefault(key, prog)
        _FUSED_PROGRAMS.move_to_end(key)
        while len(_FUSED_PROGRAMS) > _FUSED_PROGRAM_CAP:
            _FUSED_PROGRAMS.popitem(last=False)
        return got


class PlanTable:
    """Precomputed lookup table (§5.2 'Complexity'): one-step lookahead
    plans for every single-event scenario from the current configuration —
    any task losing one worker, a worker joining, a task finishing —
    giving O(1) dispatch when the event actually happens.

    Incremental build: base reward rows G(t_i, ·) at the largest scenario
    budget are computed once from the memoized cost-model curves, prefix
    DPs P[i] (tasks 0..i-1) and suffix DPs T[i] (tasks i..m-1) are each one
    max-plus pass, and every scenario is then assembled from them:

      fault:i   combine(P[i], fault-row_i, T[i+1])   (2 convolutions)
      join:1    combine(P[m//2], T[m//2])             (1 convolution)
      finish:i  combine(P[i], T[i+1])                 (1 convolution)

    ``lazy=True`` defers scenario assembly (and the node merges / chains
    feeding it) to the first ``lookup`` of each key: a table consulted for
    one scenario before the cluster state changes again only pays for that
    scenario.  A ``PlannerCache`` shares rows and node/chain vectors
    *across* rebuilds.  The batched engine additionally separates values
    from assignments: ``rebuild_values()`` materializes every scenario's
    total in a constant number of stacked kernel launches per tree level,
    and the O(m) argmax traceback runs only for keys ``lookup`` actually
    dispatches.

    ``incremental=False`` retains the original scenario-by-scenario full
    solves (the reference path the tests and benchmarks compare against).
    """

    #: canonical engine names — aliases the module-level registry tuple
    ENGINES = ENGINES

    def __init__(self, tasks: Sequence[Task], assignment: Sequence[int],
                 hw: Hardware, d_running: float, d_transition: float,
                 workers_per_fault: int = 8, incremental: bool = True,
                 solver=None, lazy: bool = False,
                 cache: Optional["PlannerCache"] = None,
                 n_budget: Optional[int] = None,
                 engine: Optional[str] = None):
        """``engine`` (canonical axis, values from
        ``engines()["engine"]``): ``"batched"`` (default;
        level-synchronous stacked merges, shared complement sweep,
        value-only assembly with lazy traceback), ``"fused"`` (the
        one-program engine: the whole-table value rebuild is a single
        jitted ``lax.scan`` dispatch, cached per schedule signature;
        lazy single lookups and tracebacks share the batched host
        machinery), ``"segtree"`` (the
        PR-3 per-node dyadic tree, O(log m) invalidation per churn step,
        one kernel call per merge), ``"chain"`` (the PR-2 prefix/suffix
        DP chains, kept as the churn-rebuild baseline) or
        ``"reference"`` (non-incremental: one full ``solve_reference``
        solve per scenario — the all-scalar ground truth).

        Deprecated shims, normalized by ``resolve_engine``:
        ``incremental=False`` falls back to one full solve per scenario
        (historical default solver: vectorized ``solve``), and a
        non-None ``solver=`` picks the per-scenario solver; both resolve
        to the ``"reference"`` engine.

        ``n_budget``: size the DP value arrays for this many workers (>=
        the largest scenario budget).  Plans are unchanged — every
        scenario argmax is sliced to its own budget — but a *fixed*
        budget (e.g. cluster capacity + one node) keeps chain-cache keys
        and array shapes identical across rebuilds at different totals."""
        requested = engine
        engine = resolve_engine(engine, solver=solver,
                                incremental=incremental)
        self.tasks = tuple(tasks)
        self.assignment = tuple(assignment)
        self.hw = hw
        self.d_running = d_running
        self.d_transition = d_transition
        self.workers_per_fault = workers_per_fault  # a node drain = 8 GPUs
        self.n_budget = n_budget
        self.engine = engine
        if engine == "reference" and requested == "reference":
            # the canonical spelling defaults to the scalar ground truth;
            # the incremental=False shim keeps its historical vectorized
            # per-scenario default
            self._solver = solver or solve_reference
        else:
            self._solver = solver or solve
        self._cache = cache
        self.table: Dict[str, Plan] = {}
        # batched/fused-engine accounting (zeros for the other engines):
        # tree/complement levels merged, stacked kernel launches issued,
        # plans materialized by on-demand traceback, and compiled fused
        # programs executed (exactly 1 per whole-table fused rebuild).
        self.batch_stats: Dict[str, int] = {"levels": 0, "launches": 0,
                                            "tracebacks": 0,
                                            "device_dispatches": 0}
        self._incremental = (engine != "reference"
                             and len(self.tasks) > 0
                             and _vector_capable(self.tasks))
        if self._incremental:
            self._init_incremental()
            if not lazy:
                if engine in ("batched", "fused"):
                    self._ensure_values()
                for key in self.scenario_keys():
                    self.lookup(key)
        else:
            self._precompute_reference()

    def scenario_keys(self) -> List[str]:
        m = len(self.tasks)
        return ([f"fault:{i}" for i in range(m)] + ["join:1"]
                + [f"finish:{i}" for i in range(m)])

    def _scenario_input(self, n_workers: int,
                        faulted_task: Optional[int]) -> PlanInput:
        faulted = tuple(i == faulted_task for i in range(len(self.tasks)))
        return PlanInput(self.tasks, self.assignment, n_workers,
                         self.d_running, self.d_transition, faulted)

    # ---- reference build: one full solve per scenario ---------------------

    def _precompute_reference(self) -> None:
        n_now = sum(self.assignment)
        w = self.workers_per_fault
        for ti in range(len(self.tasks)):
            key = f"fault:{ti}"
            self.table[key] = self._solver(
                self._scenario_input(max(n_now - w, 0), ti), self.hw)
        self.table["join:1"] = self._solver(
            self._scenario_input(n_now + w, None), self.hw)
        for ti in range(len(self.tasks)):
            # task ti finished: its workers return to the pool
            rem_tasks = self.tasks[:ti] + self.tasks[ti + 1:]
            rem_assign = self.assignment[:ti] + self.assignment[ti + 1:]
            inp = PlanInput(rem_tasks, rem_assign, n_now,
                            self.d_running, self.d_transition,
                            (False,) * len(rem_tasks))
            self.table[f"finish:{ti}"] = self._solver(inp, self.hw)

    # ---- incremental build: shared rows + prefix/suffix DP chains ---------

    def _init_incremental(self) -> None:
        m = len(self.tasks)
        n_now = sum(self.assignment)
        w = self.workers_per_fault
        self._n_now = n_now
        self._n_join = n_now + w                # join is the largest budget
        self._n_max = max(self._n_join, self.n_budget or 0)
        self._n_fault = max(n_now - w, 0)
        self._rows: List[Optional[np.ndarray]] = [None] * m
        self._frows: Dict[int, np.ndarray] = {}
        self._P: List[Optional[np.ndarray]] = [None] * (m + 1)
        self._T: List[Optional[np.ndarray]] = [None] * (m + 1)
        self._P[0] = np.zeros(self._n_max + 1)
        self._T[m] = np.zeros(self._n_max + 1)
        # The chain engine keeps the PR-1/PR-2 kernels on purpose: that
        # path IS the preserved churn-rebuild baseline whose wall-clock
        # the bench speedup floors are measured against.  The segment
        # tree runs on the fused banded kernel (backend-dispatched);
        # outputs of all kernels are bitwise identical on the same
        # candidate sets.
        self._conv = _maxplus_vals_fast if self._cache else _maxplus_vals
        self._V: Dict[Tuple[int, int], np.ndarray] = {}
        self._sat_memo: Dict[Tuple[int, int], int] = {}
        # batched engine: complement vectors per tree node (Comp(X) =
        # merge of X's root-path siblings), their cumulative saturations
        # and sibling paths, plus value-only scenario results
        # (vector, argmax cell, total) pending lazy traceback.
        self._Comp: Dict[Tuple[int, int], np.ndarray] = {}
        self._csat: Dict[Tuple[int, int], int] = {}
        self._csibs: Dict[Tuple[int, int], Tuple] = {}
        self._scen: Dict[str, Tuple[np.ndarray, int, float]] = {}
        self._level_nodes: Optional[List[List[Tuple[int, int]]]] = None
        self._tree_built = False
        self._values_built = False
        cache = self._cache
        if cache is not None:
            self._pairs = tuple((cache.task_id(t), x)
                                for t, x in zip(self.tasks,
                                                self.assignment))
            self._sig = (self.hw, self._n_max, self.d_running,
                         self.d_transition)

    def _pkey(self, i: int):
        return ("P", self._sig, self._pairs[:i])

    def _skey(self, i: int):
        return ("T", self._sig, self._pairs[i:])

    def _rkey(self, i: int, faulted: bool):
        return ("G", self._sig, self._pairs[i], faulted)

    def _row(self, i: int, faulted: bool = False) -> np.ndarray:
        store = self._frows if faulted else self._rows
        row = store.get(i) if faulted else store[i]
        if row is not None:
            return row

        def build() -> np.ndarray:
            return waf_mod.reward_curve(
                self.tasks[i], self.assignment[i], self._n_max,
                d_running=self.d_running, d_transition=self.d_transition,
                worker_faulted=faulted, hw=self.hw)

        if self._cache is not None:
            row = self._cache.array(self._rkey(i, faulted), build)
        else:
            row = build()
        store[i] = row
        return row

    def _prefix(self, i: int) -> np.ndarray:
        """P[i]: DP value vector over tasks 0..i-1 (cache-chained)."""
        start = i
        while self._P[start] is None:
            if self._cache is not None:
                hit = self._cache.array(self._pkey(start))
                if hit is not None:
                    self._P[start] = hit
                    break
            start -= 1
        for t in range(start + 1, i + 1):
            if self._P[t] is None:
                arr = self._conv(self._P[t - 1], self._row(t - 1))
                if self._cache is not None:
                    self._cache.array(self._pkey(t), lambda: arr)
                self._P[t] = arr
        return self._P[i]

    def _suffix(self, i: int) -> np.ndarray:
        """T[i]: DP value vector over tasks i..m-1 (cache-chained)."""
        start = i
        while self._T[start] is None:
            if self._cache is not None:
                hit = self._cache.array(self._skey(start))
                if hit is not None:
                    self._T[start] = hit
                    break
            start += 1
        for t in range(start - 1, i - 1, -1):
            if self._T[t] is None:
                arr = self._conv(self._T[t + 1], self._row(t))
                if self._cache is not None:
                    self._cache.array(self._skey(t), lambda: arr)
                self._T[t] = arr
        return self._T[i]

    def _cwaf(self, tasks: Sequence[Task], assign: Sequence[int]) -> float:
        """Cluster WAF of an assembled plan.  With a cache, reads F(t, ·)
        vectors (same floats as the scalar ``waf`` — the sweep mirrors the
        scalar arithmetic) instead of per-(task, x) model evaluations."""
        if self._cache is None:
            return _cluster_waf(tasks, assign, self.hw)
        total = 0.0
        for t, x in zip(tasks, assign):
            F = self._cache.array(
                ("F", self.hw, self._cache.task_id(t)),
                lambda t=t: waf_mod.waf_curve(t, self._n_max, self.hw))
            x = int(x)
            if x < F.shape[0]:
                total += float(F[x])
            else:
                total += waf_mod.waf(t, x, self.hw)
        return total

    def _walk_prefix(self, last: int, budget: int,
                     assign: List[int]) -> None:
        for t in range(last, -1, -1):
            k = _argmax_at(self._prefix(t), self._row(t), budget)
            assign[t] = k
            budget -= k

    def _walk_suffix(self, first: int, budget: int, assign: List[int],
                     offset: int = 0) -> None:
        for t in range(first, len(self.tasks)):
            k = _argmax_at(self._suffix(t + 1), self._row(t), budget)
            assign[t - offset] = k
            budget -= k

    def _assemble_chain(self, key: str) -> Optional[Plan]:
        """Build one scenario plan from the shared rows and P/T chains
        (same combine order and tie-breaking as the eager build)."""
        m = len(self.tasks)
        if key == "join:1":
            # combine at the mid split so both chain halves stay reusable
            # across rebuilds (a change at position i only invalidates the
            # half containing i)
            s = m // 2
            combined = self._conv(self._prefix(s), self._suffix(s))
            j = int(np.argmax(combined[:self._n_join + 1]))
            assign = [0] * m
            b = _argmax_at(self._prefix(s), self._suffix(s), j)
            self._walk_prefix(s - 1, j - b, assign)
            self._walk_suffix(s, b, assign)
            return Plan(tuple(assign), float(combined[j]),
                        self._cwaf(self.tasks, assign))
        kind, _, idx = key.partition(":")
        if not idx.isdigit():
            return None
        ti = int(idx)
        if not 0 <= ti < m:
            return None
        if kind == "fault":
            frow = self._row(ti, faulted=True)
            mid = None
            if self._cache is not None:    # P[ti] (+) fault-row, by prefix
                mid = self._cache.array(("M", self._sig,
                                         self._pairs[:ti + 1]))
            if mid is None:
                mid = self._conv(self._prefix(ti), frow)
                if self._cache is not None:
                    self._cache.array(("M", self._sig,
                                       self._pairs[:ti + 1]), lambda: mid)
            combined = self._conv(mid, self._suffix(ti + 1))
            j = int(np.argmax(combined[:self._n_fault + 1]))
            total = float(combined[j])
            assign = [0] * m
            b = _argmax_at(mid, self._suffix(ti + 1), j)   # suffix budget
            k = _argmax_at(self._prefix(ti), frow, j - b)  # faulted task
            assign[ti] = k
            self._walk_prefix(ti - 1, j - b - k, assign)
            self._walk_suffix(ti + 1, b, assign)
            return Plan(tuple(assign), total,
                        self._cwaf(self.tasks, assign))
        if kind == "finish":
            combined = self._conv(self._prefix(ti), self._suffix(ti + 1))
            j = int(np.argmax(combined[:self._n_now + 1]))
            total = float(combined[j])
            assign = [0] * (m - 1)
            b = _argmax_at(self._prefix(ti), self._suffix(ti + 1), j)
            self._walk_prefix(ti - 1, j - b, assign)
            self._walk_suffix(ti + 1, b, assign, offset=1)
            rem = self.tasks[:ti] + self.tasks[ti + 1:]
            return Plan(tuple(assign), total, self._cwaf(rem, assign))
        return None

    # ---- segment-tree engine: dyadic span merges + complement chains ------

    def _band(self, i: int, faulted: bool = False) -> Optional[int]:
        """Band of task i's reward row: the row is flat past it (worker
        cap; plus the unfaulted row's no-transition spike at x_old), so
        banded convolutions with it are exact.  None = uncapped/dense."""
        cap = self.tasks[i].max_workers
        if cap is None:
            return None
        b = min(max(cap, 0), self._n_max)
        if not faulted:                    # g[x_old] spike breaks flatness
            b = min(max(b, self.assignment[i]), self._n_max)
        return b

    def _sat(self, lo: int, hi: int) -> int:
        """Saturation of span [lo, hi): V[lo, hi) is flat past the sum of
        its tasks' bands (more workers than every cap combined are idle).
        Memoized per table — the level sweeps consult every node's
        saturation repeatedly."""
        got = self._sat_memo.get((lo, hi))
        if got is not None:
            return got
        s = 0
        for i in range(lo, hi):
            b = self._band(i)
            s += self._n_max if b is None else b
            if s >= self._n_max:
                s = self._n_max
                break
        self._sat_memo[(lo, hi)] = s
        return s

    def _vkey(self, lo: int, hi: int):
        return ("V", self._sig, self._pairs[lo:hi])

    def _vvec(self, lo: int, hi: int) -> np.ndarray:
        """V[lo, hi): max-plus merge of the span's reward rows (best span
        reward using at most j workers), built by dyadic midpoint split
        and cached by span *contents* — a churn step at task u only
        invalidates the O(log m) spans containing u."""
        got = self._V.get((lo, hi))
        if got is not None:
            return got
        arr = None
        if self._cache is not None:
            arr = self._cache.array(self._vkey(lo, hi))
        if arr is None:
            if hi - lo == 1:
                arr = np.maximum.accumulate(self._row(lo))
            else:
                mid = (lo + hi) // 2
                left, right = self._vvec(lo, mid), self._vvec(mid, hi)
                sl, sr = self._sat(lo, mid), self._sat(mid, hi)
                if sl < sr:               # band by the flatter operand
                    arr = _conv_vals(right, left,
                                     sl if sl < self._n_max else None)
                else:
                    arr = _conv_vals(left, right,
                                     sr if sr < self._n_max else None)
            if self._cache is not None:
                self._cache.array(self._vkey(lo, hi), lambda: arr)
        self._V[(lo, hi)] = arr
        return arr

    def _path_sibs(self, ti: int) -> List[Tuple[int, int]]:
        """Siblings along the root -> leaf(ti) path, top-down: their
        union is every task except ti."""
        sibs: List[Tuple[int, int]] = []
        lo, hi = 0, len(self.tasks)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if ti < mid:
                sibs.append((mid, hi))
                hi = mid
            else:
                sibs.append((lo, mid))
                lo = mid
        return sibs

    def _ckey(self, sibs: Sequence[Tuple[int, int]]):
        return ("C", self._sig, tuple(self._pairs[a:b] for a, b in sibs))

    def _compl_chain(self, ti: int):
        """Complement chain of leaf ti: Cs[i] merges the first i root-path
        siblings, so Cs[-1] is the DP value vector over every task except
        ti (the ``finish:ti`` vector, and the ``fault:ti`` base)."""
        sibs = self._path_sibs(ti)
        Cs = [np.zeros(self._n_max + 1)]
        satc = 0
        for i, (a, b) in enumerate(sibs):
            C = None
            if self._cache is not None:
                C = self._cache.array(self._ckey(sibs[: i + 1]))
            if C is None:
                sat_v = self._sat(a, b)
                if satc < sat_v:          # band by the flatter operand
                    C = _conv_vals(self._vvec(a, b), Cs[i],
                                   satc if satc < self._n_max else None)
                else:
                    C = _conv_vals(Cs[i], self._vvec(a, b),
                                   sat_v if sat_v < self._n_max else None)
                if self._cache is not None:
                    self._cache.array(self._ckey(sibs[: i + 1]), lambda: C)
            satc = min(satc + self._sat(a, b), self._n_max)
            Cs.append(C)
        return sibs, Cs

    def _walk_span(self, lo: int, hi: int, budget: int,
                   assign: List[int]) -> None:
        """Traceback inside span [lo, hi): recover the per-task workers
        achieving V[lo, hi)[budget] by descending the tree (first-max
        splits, like the chain walks)."""
        if hi - lo == 1:
            assign[lo] = int(np.argmax(self._row(lo)[:budget + 1]))
            return
        mid = (lo + hi) // 2
        b = _argmax_at(self._vvec(lo, mid), self._vvec(mid, hi), budget)
        self._walk_span(mid, hi, b, assign)
        self._walk_span(lo, mid, budget - b, assign)

    def _walk_compl(self, sibs, Cs, budget: int,
                    assign: List[int]) -> None:
        for i in range(len(sibs) - 1, -1, -1):
            a, b_hi = sibs[i]
            b = _argmax_at(Cs[i], self._vvec(a, b_hi), budget)
            self._walk_span(a, b_hi, b, assign)
            budget -= b

    def _assemble_segtree(self, key: str) -> Optional[Plan]:
        """Build one scenario plan from O(log m) cached node merges."""
        m = len(self.tasks)
        if key == "join:1":
            root = self._vvec(0, m)
            j = int(np.argmax(root[:self._n_join + 1]))
            assign = [0] * m
            self._walk_span(0, m, j, assign)
            return Plan(tuple(assign), float(root[j]),
                        self._cwaf(self.tasks, assign))
        kind, _, idx = key.partition(":")
        if not idx.isdigit():
            return None
        ti = int(idx)
        if not 0 <= ti < m:
            return None
        if kind not in ("fault", "finish"):
            return None
        sibs, Cs = self._compl_chain(ti)
        C = Cs[-1]
        if kind == "fault":
            frow = self._row(ti, faulted=True)
            combined = None
            fkey = None
            if self._cache is not None:
                fkey = self._fm_key(ti)
                combined = self._cache.array(fkey)
            if combined is None:
                combined = _conv_vals(C, frow, self._band(ti, faulted=True))
                if self._cache is not None:
                    self._cache.array(fkey, lambda: combined)
            j = int(np.argmax(combined[:self._n_fault + 1]))
            total = float(combined[j])
            assign = [0] * m
            k = _argmax_at(C, frow, j)
            assign[ti] = k
            self._walk_compl(sibs, Cs, j - k, assign)
            return Plan(tuple(assign), total,
                        self._cwaf(self.tasks, assign))
        j = int(np.argmax(C[:self._n_now + 1]))
        total = float(C[j])
        assign = [0] * m
        self._walk_compl(sibs, Cs, j, assign)
        del assign[ti]
        rem = self.tasks[:ti] + self.tasks[ti + 1:]
        return Plan(tuple(assign), total, self._cwaf(rem, assign))

    # ---- batched engine: level-synchronous stacked sweeps + lazy traceback -

    def _fm_key(self, ti: int):
        """Cache key of the ``fault:ti`` combined vector (cache only)."""
        return ("FM", self._sig,
                (self._pairs[:ti], self._pairs[ti + 1:]), self._pairs[ti])

    def _levels(self) -> List[List[Tuple[int, int]]]:
        """Dyadic tree nodes grouped by depth (root first), memoized."""
        if self._level_nodes is None:
            out: List[List[Tuple[int, int]]] = []

            def walk(lo: int, hi: int, d: int) -> None:
                if len(out) <= d:
                    out.append([])
                out[d].append((lo, hi))
                if hi - lo > 1:
                    mid = (lo + hi) // 2
                    walk(lo, mid, d + 1)
                    walk(mid, hi, d + 1)

            walk(0, len(self.tasks), 0)
            self._level_nodes = out
        return self._level_nodes

    def _launch(self, rows: List[Tuple[np.ndarray, np.ndarray,
                                       Optional[int]]]) -> np.ndarray:
        """One stacked kernel launch over ``rows`` of (prev, g, band).
        A single-row level skips the stacking machinery — the 2-D kernel
        is the identical computation (and tiny tables are all single-row
        levels)."""
        self.batch_stats["launches"] += 1
        if len(rows) == 1:
            prev, g, band = rows[0]
            return _conv_vals(prev, g, band)[None, :]
        prev = np.stack([r[0] for r in rows])
        g = np.stack([r[1] for r in rows])
        return _conv_vals_batched(prev, g, [r[2] for r in rows])

    def _node_hit(self, lo: int, hi: int) -> Optional[np.ndarray]:
        got = self._V.get((lo, hi))
        if got is None and self._cache is not None:
            got = self._cache.array(self._vkey(lo, hi))
            if got is not None:
                self._V[(lo, hi)] = got
        return got

    def _store_node(self, lo: int, hi: int, arr: np.ndarray) -> None:
        self._V[(lo, hi)] = arr
        if self._cache is not None:
            self._cache.array(self._vkey(lo, hi), lambda: arr)

    def _build_spans(self, roots: List[Tuple[int, int, int]]) -> None:
        """Level-synchronous V build of the given (lo, hi, depth)
        subtrees: descend pruning spans the cache already holds, build
        every missing leaf as one vectorized running-max pass, then merge
        each level's internal nodes with ONE stacked banded launch,
        bottom-up.  Same merges, operand orders and bands as ``_vvec`` —
        floats are identical.  Depths are global tree depths, so nodes of
        different subtrees land in shared level launches."""
        roots = [r for r in roots if (r[0], r[1]) not in self._V]
        if not roots:
            return
        need: List[List[Tuple[int, int]]] = [[] for _ in self._levels()]

        def visit(lo: int, hi: int, d: int) -> None:
            if self._node_hit(lo, hi) is not None:
                return
            need[d].append((lo, hi))
            if hi - lo > 1:
                mid = (lo + hi) // 2
                visit(lo, mid, d + 1)
                visit(mid, hi, d + 1)

        for lo, hi, d in roots:
            visit(lo, hi, d)
        leaves = [nd for lvl in need for nd in lvl if nd[1] - nd[0] == 1]
        if leaves:
            rows = np.stack([self._row(lo) for lo, _ in leaves])
            acc = np.maximum.accumulate(rows, axis=1)
            for r, (lo, hi) in enumerate(leaves):
                self._store_node(lo, hi, acc[r])
        for d in range(len(need) - 1, -1, -1):
            todo = [nd for nd in need[d] if nd[1] - nd[0] > 1]
            if not todo:
                continue
            stack = []
            for lo, hi in todo:
                mid = (lo + hi) // 2
                left, right = self._V[(lo, mid)], self._V[(mid, hi)]
                sl, sr = self._sat(lo, mid), self._sat(mid, hi)
                if sl < sr:               # band by the flatter operand
                    stack.append((right, left,
                                  sl if sl < self._n_max else None))
                else:
                    stack.append((left, right,
                                  sr if sr < self._n_max else None))
            out = self._launch(stack)
            self.batch_stats["levels"] += 1
            for r, (lo, hi) in enumerate(todo):
                self._store_node(lo, hi, out[r])

    def _ensure_tree(self) -> None:
        """Whole-tree V sweep (the join scenario and the whole-table
        value rebuild consume every node)."""
        if self._tree_built:
            return
        self._build_spans([(0, len(self.tasks), 0)])
        self._tree_built = True

    def _ensure_chain_spans(self, ti: int) -> None:
        """Build exactly the sibling subtrees leaf ti's complement chain
        merges — the same node set the segtree engine's recursive
        ``_vvec`` calls would touch for this scenario, but launched per
        level instead of per node.  Single cold dispatches therefore
        never pay for the root-path merges only ``join`` needs."""
        missing = [(a, b, i + 1)
                   for i, (a, b) in enumerate(self._path_sibs(ti))
                   if (a, b) not in self._V]
        if missing:
            self._build_spans(missing)

    def _comp_meta(self, child: Tuple[int, int], parent: Tuple[int, int],
                   sib: Tuple[int, int]) -> None:
        """Sibling path and cumulative saturation of a comp-tree child."""
        self._csibs[child] = self._csibs[parent] + (sib,)
        self._csat[child] = min(self._csat[parent] + self._sat(*sib),
                                self._n_max)

    def _comp_root(self) -> Tuple[int, int]:
        root = (0, len(self.tasks))
        if root not in self._Comp:
            self._Comp[root] = np.zeros(self._n_max + 1)
        self._csat.setdefault(root, 0)
        self._csibs.setdefault(root, ())
        return root

    def _total_entry(self, vec: np.ndarray,
                     limit: int) -> Tuple[np.ndarray, int, float]:
        j = int(np.argmax(vec[:limit + 1]))
        return vec, j, float(vec[j])

    def _ensure_values(self) -> None:
        """Whole-table value rebuild: the complement vector of EVERY tree
        node via one top-down level-parallel sweep (all children of a
        level in one stacked launch — the m per-leaf chains overlap in
        exactly these O(m) distinct nodes, so nothing is recomputed per
        scenario), then all m fault combines in one more launch, then
        every scenario's total.  NO argmax tracebacks — ``lookup`` runs
        those lazily for the scenario actually dispatched.

        On the fused engine the identical sweep (same operands, orders
        and bands) runs as ONE compiled device dispatch instead."""
        if self._values_built:
            return
        if self.engine == "fused":
            self._ensure_values_fused()
            return
        self._ensure_tree()
        m = len(self.tasks)
        self._comp_root()
        levels = self._levels()
        for d in range(len(levels) - 1):
            todo, stack = [], []
            for lo, hi in levels[d]:
                if hi - lo == 1:
                    continue
                mid = (lo + hi) // 2
                for child, sib in (((lo, mid), (mid, hi)),
                                   ((mid, hi), (lo, mid))):
                    self._comp_meta(child, (lo, hi), sib)
                    if child in self._Comp:
                        continue
                    C = None
                    if self._cache is not None:
                        C = self._cache.array(
                            self._ckey(self._csibs[child]))
                    if C is not None:
                        self._Comp[child] = C
                        continue
                    satc = self._csat[(lo, hi)]
                    sat_v = self._sat(*sib)
                    if satc < sat_v:      # band by the flatter operand
                        stack.append((self._vvec(*sib), self._Comp[(lo, hi)],
                                      satc if satc < self._n_max else None))
                    else:
                        stack.append((self._Comp[(lo, hi)], self._vvec(*sib),
                                      sat_v if sat_v < self._n_max else None))
                    todo.append(child)
            if todo:
                out = self._launch(stack)
                self.batch_stats["levels"] += 1
                for r, child in enumerate(todo):
                    arr = out[r]
                    self._Comp[child] = arr
                    if self._cache is not None:
                        self._cache.array(self._ckey(self._csibs[child]),
                                          lambda a=arr: a)
        todo, stack = [], []
        for ti in range(m):
            key = f"fault:{ti}"
            if key in self._scen:
                continue
            combined = None
            if self._cache is not None:
                combined = self._cache.array(self._fm_key(ti))
            if combined is not None:
                self._scen[key] = self._total_entry(combined, self._n_fault)
                continue
            stack.append((self._Comp[(ti, ti + 1)],
                          self._row(ti, faulted=True),
                          self._band(ti, faulted=True)))
            todo.append(ti)
        if todo:
            out = self._launch(stack)
            for r, ti in enumerate(todo):
                arr = out[r]
                if self._cache is not None:
                    self._cache.array(self._fm_key(ti), lambda a=arr: a)
                self._scen[f"fault:{ti}"] = self._total_entry(
                    arr, self._n_fault)
        for ti in range(m):
            self._scen.setdefault(f"finish:{ti}", self._total_entry(
                self._Comp[(ti, ti + 1)], self._n_now))
        self._scen.setdefault("join:1", self._total_entry(
            self._vvec(0, m), self._n_join))
        self._values_built = True

    def _fused_signature(self) -> Tuple:
        """Schedule signature of this table: the static inputs the
        compiled fused program is keyed (and retraced) on.  Bands are
        normalized to ``n_max`` for uncapped/dense rows."""
        m = len(self.tasks)
        bu = tuple(self._n_max if b is None else b
                   for b in (self._band(i) for i in range(m)))
        bf = tuple(self._n_max if b is None else b
                   for b in (self._band(i, faulted=True)
                             for i in range(m)))
        return (m, self._n_max, bu, bf, get_maxplus_backend())

    def _ensure_values_fused(self) -> None:
        """Whole-table value rebuild as ONE compiled device dispatch:
        fetch (or build) the signature-keyed fused program, hand it the
        reward-row stacks and per-scenario argmax limits, and unpack the
        returned slot buffer into the batched engine's stores — the
        host-side lazy traceback machinery then works unchanged.  Node
        vectors are deliberately NOT written to the ``PlannerCache``
        array store: on this path the program cache is the reuse
        mechanism, and a recurring cluster state is already a whole-table
        hit at the ``PlannerCache.table`` level."""
        m = len(self.tasks)
        prog = _fused_program(*self._fused_signature())
        with obs.span("plan.rows"):
            g_unf = np.stack([np.asarray(self._row(i), dtype=float)
                              for i in range(m)])
            g_f = np.stack([np.asarray(self._row(i, faulted=True),
                                       dtype=float) for i in range(m)])
        limits = np.asarray([self._n_fault] * m + [self._n_now] * m
                            + [self._n_join], dtype=np.int32)
        with obs.span("plan.program"):
            vals, js, totals = prog(g_unf, g_f, limits)
        self.batch_stats["device_dispatches"] += 1
        sched = prog.sched
        with obs.span("plan.unpack"):
            for node, si in sched.v_slot.items():
                self._V[node] = vals[si]
            self._comp_root()
            for node, si in sched.c_slot.items():
                self._Comp.setdefault(node, vals[si])
            self._sat_memo.update(sched.sat_map)
            self._csat.update(sched.csat_map)
            self._csibs.update(sched.csibs_map)
            for ti in range(m):
                self._scen.setdefault(
                    f"fault:{ti}", (vals[sched.fault_slot[ti]],
                                    int(js[ti]), float(totals[ti])))
                self._scen.setdefault(
                    f"finish:{ti}", (self._Comp[(ti, ti + 1)],
                                     int(js[m + ti]), float(totals[m + ti])))
            self._scen.setdefault("join:1", (self._V[(0, m)], int(js[2 * m]),
                                             float(totals[2 * m])))
        self._tree_built = True
        self._values_built = True

    def _chain_batched(self, ti: int):
        """(sibs, Cs) complement chain of leaf ti, reading the level-sweep
        store and computing (and storing) only missing links — the
        single-dispatch path shares every vector with the whole-table
        sweep (same operands, orders and bands: identical floats).

        Like the segtree engine's chain, a cached link costs nothing:
        the sibling V subtrees are only built — one stacked level launch
        per level, restricted to the missing siblings — past the longest
        already-known chain prefix."""
        sibs = self._path_sibs(ti)
        path = [self._comp_root()]
        for a, b in sibs:
            lo, hi = path[-1]
            mid = (lo + hi) // 2
            path.append((lo, mid) if (a, b) == (mid, hi) else (mid, hi))
        Cs = [self._Comp[path[0]]]
        known = 0
        for i, (sib, child) in enumerate(zip(sibs, path[1:])):
            self._comp_meta(child, path[i], sib)
            C = self._Comp.get(child)
            if C is None and self._cache is not None:
                C = self._cache.array(self._ckey(self._csibs[child]))
                if C is not None:
                    self._Comp[child] = C
            if C is None:
                break
            Cs.append(C)
            known = i + 1
        if known == len(sibs):
            return sibs, Cs
        self._build_spans([(a, b, i + 1)
                           for i, (a, b) in enumerate(sibs)
                           if i >= known and (a, b) not in self._V])
        for i in range(known, len(sibs)):
            a, b = sibs[i]
            child = path[i + 1]
            self._comp_meta(child, path[i], (a, b))
            C = self._Comp.get(child)
            if C is None and self._cache is not None:
                C = self._cache.array(self._ckey(self._csibs[child]))
            if C is None:
                satc = self._csat[path[i]]
                sat_v = self._sat(a, b)
                if satc < sat_v:          # band by the flatter operand
                    C = _conv_vals(self._vvec(a, b), Cs[-1],
                                   satc if satc < self._n_max else None)
                else:
                    C = _conv_vals(Cs[-1], self._vvec(a, b),
                                   sat_v if sat_v < self._n_max else None)
                if self._cache is not None:
                    self._cache.array(self._ckey(self._csibs[child]),
                                      lambda: C)
            self._Comp[child] = C
            Cs.append(C)
        return sibs, Cs

    def _fault_combined(self, ti: int, C: np.ndarray) -> np.ndarray:
        """``fault:ti`` combined vector: C(leaf ti) (+) fault-row, cache
        -shared with the whole-table sweep."""
        combined = None
        if self._cache is not None:
            combined = self._cache.array(self._fm_key(ti))
        if combined is None:
            combined = _conv_vals(C, self._row(ti, faulted=True),
                                  self._band(ti, faulted=True))
            if self._cache is not None:
                self._cache.array(self._fm_key(ti), lambda: combined)
        return combined

    def _parse_leaf_key(self, key: str) -> Optional[Tuple[str, int]]:
        kind, _, idx = key.partition(":")
        if kind not in ("fault", "finish") or not idx.isdigit():
            return None
        ti = int(idx)
        if not 0 <= ti < len(self.tasks):
            return None
        return kind, ti

    def _scen_entry(self, key: str
                    ) -> Optional[Tuple[np.ndarray, int, float]]:
        """Value-only scenario result (vector, argmax cell, total): from
        the whole-table sweep when built, else assembled for this key
        alone (single dispatches stay O(chain), not O(table))."""
        got = self._scen.get(key)
        if got is not None:
            return got
        if key == "join:1":
            self._ensure_tree()
            entry = self._total_entry(self._vvec(0, len(self.tasks)),
                                      self._n_join)
        else:
            parsed = self._parse_leaf_key(key)
            if parsed is None:
                return None
            kind, ti = parsed
            _, Cs = self._chain_batched(ti)
            if kind == "finish":
                entry = self._total_entry(Cs[-1], self._n_now)
            else:
                entry = self._total_entry(self._fault_combined(ti, Cs[-1]),
                                          self._n_fault)
        self._scen[key] = entry
        return entry

    def _assemble_batched(self, key: str) -> Optional[Plan]:
        """Materialize one scenario's Plan: value vectors from the batched
        store, then the lazy argmax traceback for just this key."""
        with obs.span("plan.traceback", key=key):
            m = len(self.tasks)
            if key == "join:1":
                entry = self._scen_entry(key)
                vec, j, total = entry
                self.batch_stats["tracebacks"] += 1
                assign = [0] * m
                self._walk_span(0, m, j, assign)
                return Plan(tuple(assign), total,
                            self._cwaf(self.tasks, assign))
            parsed = self._parse_leaf_key(key)
            if parsed is None:
                return None
            kind, ti = parsed
            sibs, Cs = self._chain_batched(ti)
            entry = self._scen.get(key)
            if entry is None:
                if kind == "finish":
                    entry = self._total_entry(Cs[-1], self._n_now)
                else:
                    entry = self._total_entry(self._fault_combined(ti, Cs[-1]),
                                              self._n_fault)
                self._scen[key] = entry
            vec, j, total = entry
            self.batch_stats["tracebacks"] += 1
            # the argmax walks descend every sibling subtree, so build them
            # (level-launched; usually warm) even when the chain was cached
            self._ensure_chain_spans(ti)
            assign = [0] * m
            if kind == "fault":
                k = _argmax_at(Cs[-1], self._row(ti, faulted=True), j)
                assign[ti] = k
                self._walk_compl(sibs, Cs, j - k, assign)
                return Plan(tuple(assign), total,
                            self._cwaf(self.tasks, assign))
            self._walk_compl(sibs, Cs, j, assign)
            del assign[ti]
            rem = self.tasks[:ti] + self.tasks[ti + 1:]
            return Plan(tuple(assign), total, self._cwaf(rem, assign))

    def rebuild_values(self) -> Dict[str, float]:
        """Whole-table value rebuild: every scenario's value vector and
        total reward with NO assignment tracebacks.  Batched engine: a
        constant number of stacked launches per tree level; fused
        engine: ONE compiled device dispatch
        (``batch_stats["device_dispatches"]``).  Returns ``{scenario
        key: total reward}``.  The other engines (and the reference
        path) fall back to materializing every plan — that per-scenario
        cost is exactly what the whole-table churn benchmark measures
        against."""
        if self.engine in ("batched", "fused") and self._incremental:
            self._ensure_values()
            return {k: self._scen[k][2] for k in self.scenario_keys()}
        out: Dict[str, float] = {}
        for k in self.scenario_keys():
            plan = self.lookup(k)
            if plan is not None:
                out[k] = plan.total_reward
        return out

    def scenario_total(self, key: str) -> Optional[float]:
        """Total reward of one scenario without materializing its
        assignment.  Batched/fused engines: triggers the whole-table
        value sweep (totals are a whole-table product; single dispatches
        should use ``lookup``).  The other engines assemble the full
        plan."""
        if self.engine in ("batched", "fused") and self._incremental:
            hit = self.table.get(key)
            if hit is not None:
                return hit.total_reward
            self._ensure_values()
            entry = self._scen.get(key)
            return None if entry is None else entry[2]
        plan = self.lookup(key)
        return None if plan is None else plan.total_reward

    def _assemble(self, key: str) -> Optional[Plan]:
        if self.engine in ("batched", "fused"):
            # the fused engine shares the batched host-side machinery
            # for lazy single lookups and every argmax traceback
            return self._assemble_batched(key)
        if self.engine == "segtree":
            return self._assemble_segtree(key)
        return self._assemble_chain(key)

    def lookup(self, key: str) -> Optional[Plan]:
        plan = self.table.get(key)
        if plan is None and self._incremental and key not in self.table:
            plan = self._assemble(key)
            if plan is not None:
                self.table[key] = plan
        return plan


class PlannerCache:
    """Cross-rebuild planner cache (the ROADMAP follow-up to the PR-1
    incremental engine): reward rows, prefix/suffix DP value chains, whole
    lazy ``PlanTable``s, and fresh ``solve`` plans, shared across every
    rebuild a churn-heavy simulation issues.

    * A rebuild where only one task's assignment changed finds every P
      chain up to the change and every T chain past it already cached, and
      recomputes only the remainder.
    * A *recurring* cluster state (same task set + assignment + durations)
      is a whole-table hit — its scenarios are never reassembled.
    * Fresh solves (table misses, task launches) are memoized by their
      full ``PlanInput``.

    All stores are bounded LRUs; ``stats()`` exposes hit/miss counters for
    the benchmarks.  Plans served from the cache are float-identical to an
    uncached build: keys include every input the arrays depend on.
    """

    def __init__(self, max_arrays: int = 32768, max_tables: int = 4096,
                 max_plans: int = 32768):
        self._arrays: OrderedDict = OrderedDict()
        self._tables: OrderedDict = OrderedDict()
        self._plans: OrderedDict = OrderedDict()
        self._caps = {"arrays": max_arrays, "tables": max_tables,
                      "plans": max_plans}
        self._task_ids: Dict[object, int] = {}
        self._lock = threading.RLock()
        self.hits = {"arrays": 0, "tables": 0, "plans": 0}
        self.misses = {"arrays": 0, "tables": 0, "plans": 0}

    def task_id(self, task) -> int:
        """Intern a task: chain keys hash small ints, not task objects."""
        with self._lock:
            tid = self._task_ids.get(task)
            if tid is None:
                tid = len(self._task_ids)
                self._task_ids[task] = tid
            return tid

    def _memo(self, store: OrderedDict, name: str, key, build):
        """Thread-compatible get-or-build.  The build runs outside the
        lock: concurrent Monte-Carlo seeds may duplicate a computation,
        but every entry is fully determined by its key, so whichever
        lands is identical — results never depend on scheduling."""
        with self._lock:
            got = store.get(key)
            if got is not None:
                store.move_to_end(key)
                self.hits[name] += 1
                return got
        if build is None:
            return None
        got = build()
        with self._lock:
            if key not in store:
                self.misses[name] += 1
                store[key] = got
                if len(store) > self._caps[name]:
                    store.popitem(last=False)
            else:
                got = store[key]
        return got

    def array(self, key, build=None) -> Optional[np.ndarray]:
        return self._memo(self._arrays, "arrays", key, build)

    def table(self, tasks: Sequence[Task], assignment: Sequence[int],
              hw: Hardware, d_running: float, d_transition: float,
              workers_per_fault: int = 8,
              n_budget: Optional[int] = None,
              engine: Optional[str] = None,
              task_ids: Optional[Tuple[int, ...]] = None,
              prebuild: bool = False) -> PlanTable:
        """A lazy PlanTable for this cluster state, memoized by state.
        ``engine``: canonical name from ``engines()["engine"]`` (default
        ``"batched"``; part of the memo key).  ``task_ids``: the
        already-interned ``task_id`` tuple for ``tasks`` (callers that
        refresh per event keep it across rebuilds — the task set only
        changes on churn).  ``prebuild=True`` runs the whole-table value
        rebuild before returning (idempotent; on the batched engine a
        constant number of stacked launches per tree level, value-only —
        no tracebacks): churn-driven coordinators use it to restore
        O(1)-ish dispatch for every scenario after a task set change."""
        engine = resolve_engine(engine)
        tasks, assignment = tuple(tasks), tuple(assignment)
        if task_ids is None:
            task_ids = tuple(self.task_id(t) for t in tasks)
        key = (task_ids, assignment, hw,
               d_running, d_transition, workers_per_fault, n_budget,
               engine)
        table = self._memo(
            self._tables, "tables", key,
            lambda: PlanTable(tasks, assignment, hw, d_running,
                              d_transition, workers_per_fault,
                              lazy=True, cache=self, n_budget=n_budget,
                              engine=engine))
        if prebuild:
            table.rebuild_values()
        return table

    def solve(self, inp: PlanInput, hw: Hardware) -> Plan:
        """Memoized fresh dispatch (``solve_fast`` — same plans as
        ``solve``, value-chain kernel)."""
        key = (tuple(self.task_id(t) for t in inp.tasks), inp.assignment,
               inp.n_workers, inp.d_running, inp.d_transition,
               inp.faulted, hw)
        return self._memo(self._plans, "plans", key,
                          lambda: solve_fast(inp, hw))

    def stats(self) -> Dict[str, Dict[str, int]]:
        return {"hits": dict(self.hits), "misses": dict(self.misses),
                "sizes": {"arrays": len(self._arrays),
                          "tables": len(self._tables),
                          "plans": len(self._plans)}}
