"""Max-plus convolution kernel microbenchmark (the planner's DP floor).

Per-convolution latency at n in {256, 1024, 4096} for the four kernels:

  * numpy   — ``_maxplus_vals`` (plain windowed matrix, PR-1 baseline);
  * fused   — ``_maxplus_vals_fused`` dense (tiled add+max, no (n x n)
              candidate matrix);
  * banded  — ``_maxplus_vals_fused`` at band = cap (cap = n/8, the
              ``Task.max_workers`` regime);
  * pallas  — ``kernels.maxplus.maxplus_conv`` in interpret mode (f32;
              the compiled Mosaic path needs a TPU).  On a TPU host the
              compiled kernel is timed too (``pallas_tpu_ms``);
              elsewhere that cell — like every other metric a row skips
              — is emitted as an explicit ``null`` (the key is always
              present), so ``check_regression`` skips it deliberately
              rather than by key absence.

Plus the stacked axis behind the ``engine="batched"`` PlanTable: one
``_maxplus_vals_fused_batched`` call over a (B, n+1) stack vs a Python
loop of B banded 2-D fused calls, at B in {16, 64}.  The stacked win is
a *launch-overhead* win: it is largest where per-row work is small
(n x band below the overhead crossover — exactly the per-level merge
stacks of the batched engine), and decays toward 1x where a single
row's candidate tiles already saturate the memory system (there the
stacked kernel falls through to per-row tiles, so it never loses).

Hard asserts (the harness fails loudly on a regression):

  * fused and banded outputs are bitwise identical to ``_maxplus_vals``
    on their candidate sets; the stacked kernel is bitwise identical to
    its per-slice 2-D calls; pallas (2-D and grid-batched) matches the
    f32 oracle to 1e-6;
  * at n >= 1024 and cap = n/8 the banded kernel is >= 5x faster than
    the dense convolution the engines previously always ran
    (``_maxplus_vals``) — the PR-3 acceptance floor.  ``banded_vs_fused``
    (banded against the *new* dense fused kernel) is also emitted; it
    sits near the 8x candidate-count ratio minus memory-system effects;
  * in the overhead-bound regime (n = 128, the batched engine's
    narrow-level shape) the stacked kernel is >= 2x faster than looped
    2-D fused calls at every batch >= 16 — the PR-5 acceptance floor.
    Larger-n stack rows are emitted unasserted to track the crossover.

``REPRO_BENCH_QUICK=1`` (set by ``run.py --quick``) trims the grids for
CI smoke runs.
"""
from __future__ import annotations

import os

import numpy as np

from benchmarks.common import emit, timeit
from repro.core.planner import (_maxplus_vals, _maxplus_vals_fused,
                                _maxplus_vals_fused_batched)

GRID_N = [256, 1024, 4096]
CAP_DIV = 8                    # banded regime: cap = n / 8
BANDED_FLOOR = 5.0             # banded >= 5x dense at cap <= n/8, n >= 1024
BATCH_GRID = [(128, 16), (128, 64), (256, 64), (1024, 64)]   # (n, B)
BATCH_FLOOR = 2.0              # stacked >= 2x looped at n = 128, B >= 16
BATCH_FLOOR_N = 128
PALLAS_TOL = 1e-6

COLUMNS = ["workers", "cap", "batch", "numpy_ms", "fused_ms", "banded_ms",
           "pallas_interp_ms", "pallas_tpu_ms", "fused_speedup",
           "banded_speedup", "banded_vs_fused", "stacked_ms", "looped_ms",
           "stack_speedup"]


def _full_row(**cells) -> dict:
    """Row with EVERY column present: metrics a grid point skips are
    explicit nulls in the JSON, never absent keys — ``check_regression``
    then skips them as deliberate "no measurement" markers."""
    row = {c: None for c in COLUMNS}
    row.update(cells)
    return row


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _data(n: int, cap: int):
    """Monotone DP vector + reward row flat past the cap (the band
    contract the planner guarantees)."""
    rng = np.random.RandomState(n)
    prev = np.maximum.accumulate(rng.uniform(0.0, 100.0, n + 1))
    g = rng.uniform(0.0, 100.0, n + 1)
    g[cap:] = g[cap]
    return prev, g


def run() -> list:
    quick = os.environ.get("REPRO_BENCH_QUICK") == "1"
    grid = [256, 1024] if quick else GRID_N
    iters = 3 if quick else 7
    rows = []
    checked_floor = False
    for n in grid:
        cap = n // CAP_DIV
        prev, g = _data(n, cap)

        want = _maxplus_vals(prev, g)
        assert np.array_equal(want, _maxplus_vals_fused(prev, g)), n
        assert np.array_equal(want,
                              _maxplus_vals_fused(prev, g, band=cap)), n

        numpy_s = timeit(_maxplus_vals, prev, g, iters=iters)
        fused_s = timeit(_maxplus_vals_fused, prev, g, iters=iters)
        banded_s = timeit(lambda: _maxplus_vals_fused(prev, g, band=cap),
                          iters=iters)

        from repro.kernels.maxplus import maxplus_conv, maxplus_conv_np
        got = np.asarray(maxplus_conv(prev, g, band=cap, interpret=True))
        oracle = maxplus_conv_np(prev, g, band=cap)
        rel = np.max(np.abs(got - oracle) / np.maximum(np.abs(oracle), 1.0))
        assert rel < PALLAS_TOL, (n, rel)
        pallas_s = timeit(
            lambda: np.asarray(
                maxplus_conv(prev, g, band=cap, interpret=True)),
            iters=iters)
        # the compiled Mosaic kernel only exists on a TPU host; off-TPU
        # the cell stays an explicit null
        pallas_tpu_s = None
        if _on_tpu():
            pallas_tpu_s = timeit(
                lambda: np.asarray(
                    maxplus_conv(prev, g, band=cap, interpret=False)),
                iters=iters)

        fused_speedup = numpy_s / fused_s
        banded_speedup = numpy_s / banded_s
        banded_vs_fused = fused_s / banded_s
        if n >= 1024:
            checked_floor = True
            assert banded_speedup >= BANDED_FLOOR, (
                f"banded max-plus speedup {banded_speedup:.1f}x at "
                f"(n={n}, cap={cap}) below the {BANDED_FLOOR:.0f}x floor")
            print(f"[floor check] banded speedup at (n={n}, cap={cap}): "
                  f"{banded_speedup:.1f}x vs dense numpy "
                  f"(floor {BANDED_FLOOR:.0f}x; vs fused "
                  f"{banded_vs_fused:.1f}x)")
        rows.append(_full_row(
            workers=n, cap=cap, batch=None,   # 2-D (unstacked) row
            numpy_ms=numpy_s * 1e3,
            fused_ms=fused_s * 1e3,
            banded_ms=banded_s * 1e3,
            pallas_interp_ms=pallas_s * 1e3,
            pallas_tpu_ms=None if pallas_tpu_s is None else pallas_tpu_s * 1e3,
            fused_speedup=fused_speedup,
            banded_speedup=banded_speedup,
            banded_vs_fused=banded_vs_fused,
        ))
    assert checked_floor, "grid never hit the n >= 1024 banded floor check"

    # ---- stacked axis: one batched call vs a loop of 2-D fused calls ------
    batch_grid = ([g for g in BATCH_GRID if g[0] <= 256] if quick
                  else BATCH_GRID)
    checked_batch_floor = False
    for n, batch in batch_grid:
        cap = n // CAP_DIV
        rng = np.random.RandomState(n + batch)
        prev = np.maximum.accumulate(
            rng.uniform(0.0, 100.0, (batch, n + 1)), axis=1)
        g = rng.uniform(0.0, 100.0, (batch, n + 1))
        g[:, cap:] = g[:, cap:cap + 1]
        bands = [cap] * batch

        got = _maxplus_vals_fused_batched(prev, g, bands)
        for r in range(batch):
            assert np.array_equal(
                got[r], _maxplus_vals_fused(prev[r], g[r], band=cap)), (
                n, batch, r)

        def _looped():
            for r in range(batch):
                _maxplus_vals_fused(prev[r], g[r], band=cap)

        stacked_s = timeit(
            lambda: _maxplus_vals_fused_batched(prev, g, bands),
            iters=iters, number=3)
        looped_s = timeit(_looped, iters=iters, number=3)
        stack_speedup = looped_s / stacked_s
        if n == BATCH_FLOOR_N and batch >= 16:
            checked_batch_floor = True
            assert stack_speedup >= BATCH_FLOOR, (
                f"stacked max-plus speedup {stack_speedup:.2f}x at "
                f"(n={n}, batch={batch}, cap={cap}) below the "
                f"{BATCH_FLOOR:.0f}x floor vs looped 2-D fused calls")
            print(f"[floor check] stacked speedup at (n={n}, "
                  f"batch={batch}, cap={cap}): {stack_speedup:.1f}x vs "
                  f"looped 2-D fused (floor {BATCH_FLOOR:.0f}x)")
        rows.append(_full_row(
            workers=n, cap=cap, batch=batch,
            stacked_ms=stacked_s * 1e3,
            looped_ms=looped_s * 1e3,
            stack_speedup=stack_speedup,
        ))
    assert checked_batch_floor, "grid never hit the stacked floor check"

    # grid-batched Pallas kernel: interpret-mode equivalence at the
    # smallest stack (full timing would measure the interpreter, not the
    # kernel; CI pins broader equivalence in tests/test_kernels.py)
    from repro.kernels.maxplus import maxplus_conv_batched, maxplus_conv_np
    n, batch = 64, 4
    rng = np.random.RandomState(0)
    prev = np.maximum.accumulate(
        rng.uniform(0.0, 100.0, (batch, n + 1)).astype(np.float32), axis=1)
    g = rng.uniform(0.0, 100.0, (batch, n + 1)).astype(np.float32)
    cap = n // CAP_DIV
    g[:, cap:] = g[:, cap:cap + 1]
    got = np.asarray(maxplus_conv_batched(prev, g, [cap] * batch,
                                          interpret=True))
    for r in range(batch):
        oracle = maxplus_conv_np(prev[r], g[r], band=cap)
        rel = np.max(np.abs(got[r] - oracle)
                     / np.maximum(np.abs(oracle), 1.0))
        assert rel < PALLAS_TOL, (r, rel)

    emit(rows, "maxplus", COLUMNS)
    return rows
