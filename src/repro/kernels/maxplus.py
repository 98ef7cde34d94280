"""Pallas TPU max-plus (tropical) convolution — the planner's DP kernel.

    out[j] = max_{0 <= k <= min(j, band)} prev[j-k] + g[k]

One kernel serves every entry point.  Its grid is (row tiles, output
blocks): each program owns 8 stacked rows (the sublane tile) and 128
output cells (the lane tile).  The padded ``prev`` rows and the reward
rows ``g`` sit whole in VMEM (they are O(n) f32 — a few KB at planner
scale), and the kernel folds the band with a ``fori_loop`` of fused
rotate+add+max steps, so no (n x n) candidate matrix ever exists in any
memory space and every block is (8, 128)-aligned, as Mosaic requires.
Follows the repo's execution-mode policy (``pallas_config``): compiled
via Mosaic on TPU, interpreted on CPU/GPU, ``REPRO_PALLAS_INTERPRET``/
kwarg override.

``maxplus_conv_batched`` is the stacked entry behind the
``engine="batched"`` PlanTable: a (B, n+1) stack of independent
convolutions with per-row bands runs as ONE ``pallas_call``.  Per-row
bands are applied by masking each ``g`` row to -inf past its band
(value-neutral: a masked candidate can never beat the always-present
finite k=0 candidate), so every row equals the 1-D ``maxplus_conv`` on
its own slice.

``maxplus_scan_chunk`` is the scan-compatible entry the fused
one-program planner engine (``engine="fused"``) uses as its inner step:
every operand arrives pre-gathered at a *static* chunk width, so the
same ``pallas_call`` shape serves every step of a ``lax.scan`` over the
planner's padded level schedule (see ``core.planner``'s "fused"
section).

The kernels run in float32 (planner's numpy path is float64); the
``REPRO_PLANNER_BACKEND=pallas`` switch in ``core.planner`` therefore
trades ~1e-7 relative reward precision for the TPU hot path and is
opt-in.  ``tests/test_kernels.py`` pins interpret-mode equivalence
against the numpy oracles (CI runs it under REPRO_PALLAS_INTERPRET=1 on
every PR, 2-D and batched legs both) and records the documented f32
error budget on paper-scale reward rows
(``test_maxplus_f32_error_budget_paper_scale``) — the ROADMAP's gate
before this backend could ever become the default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_config import resolve_interpret

NEG = float("-inf")
ROWS = 8      # sublane tile: stacked rows per grid program
LANES = 128   # lane tile: output cells per grid program


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _maxplus_rows_kernel(x_ref, g_ref, o_ref, *, band: int, block: int,
                         win: int):
    """o[r, j0 + dj] = max_{0 <= k <= band} x[r, j0 + band + dj - k]
    + g[r, k] for the (row tile, output block) this program owns.

    The candidate shift k is a lane offset that is not a multiple of the
    lane tile, which Mosaic cannot load directly.  So the program loads
    one aligned window of x and the whole g row block, and carries both
    through the band loop as lane rotations by one: after k steps lane
    dj of the x carry holds x[j0 + band - k + dj] and lane 0 of the g
    carry holds g[k].  Shifts are int32 even when the caller traces under
    x64, as Mosaic's rotate takes nothing wider."""
    j0 = pl.multiple_of(pl.program_id(1) * block, block)
    gw = g_ref.shape[1]
    one, g_back = np.int32(1), np.int32(gw - 1)
    xr = pltpu.roll(x_ref[:, pl.ds(j0, win)], np.int32(win - band), 1)
    gr = g_ref[...]

    def body(_, carry):
        acc, xr, gr = carry
        acc = jnp.maximum(acc, xr[:, :block] + gr[:, :1])
        return acc, pltpu.roll(xr, one, 1), pltpu.roll(gr, g_back, 1)

    init = jnp.full((x_ref.shape[0], block), NEG, dtype=jnp.float32)
    o_ref[...] = jax.lax.fori_loop(np.int32(0), np.int32(band + 1), body,
                                   (init, xr, gr))[0]


@functools.partial(jax.jit, static_argnames=("band", "n_out", "block",
                                             "interpret"))
def _maxplus_rows(x, g, band: int, n_out: int, block: int,
                  interpret: bool):
    """``out[r, j] = max_{0 <= k <= band} x[r, j + band - k] + g[r, k]``
    for ``j < n_out``: the one Pallas launch behind every entry point.
    ``x`` is (B, band + n_out) and ``g`` (B, >= band + 1), float32 with
    -inf wherever a candidate must not count.  Rows are padded to the
    sublane tile and widths to the lane tile with -inf, so every block
    is (8, 128)-aligned; padded rows and cells are sliced off."""
    B = x.shape[0]
    rows = _round_up(max(B, 1), ROWS)
    nb = max(1, -(-n_out // block))                      # cdiv
    win = _round_up(band + block, LANES)
    xw = (nb - 1) * block + win
    gw = _round_up(band + 1, LANES)
    x_pad = jnp.full((rows, xw), NEG, dtype=jnp.float32)
    x_pad = x_pad.at[:B, :x.shape[1]].set(x)
    g_pad = jnp.full((rows, gw), NEG, dtype=jnp.float32)
    g_pad = g_pad.at[:B, :band + 1].set(g[:, :band + 1])
    out = pl.pallas_call(
        functools.partial(_maxplus_rows_kernel, band=band, block=block,
                          win=win),
        grid=(rows // ROWS, nb),
        in_specs=[   # int32 block indices, also under a caller's x64
            pl.BlockSpec((ROWS, xw), lambda r, i: (r, np.int32(0))),
            pl.BlockSpec((ROWS, gw), lambda r, i: (r, np.int32(0))),
        ],
        out_specs=pl.BlockSpec((ROWS, block), lambda r, i: (r, i)),
        out_shape=jax.ShapeDtypeStruct((rows, nb * block), jnp.float32),
        interpret=interpret,
    )(x_pad, g_pad)
    return out[:B, :n_out]


def maxplus_conv(prev, g, band: Optional[int] = None, *,
                 block: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Banded max-plus convolution of ``prev`` (DP value vector) with
    ``g`` (reward row), both length n+1; returns the length-n+1 float32
    value vector.  ``band=None`` is the dense convolution; a finite band
    is exact under the planner's band contract (``prev`` monotone,
    ``g`` flat past the band).  A one-row ``maxplus_conv_batched``."""
    prev = jnp.asarray(prev, dtype=jnp.float32)
    g = jnp.asarray(g, dtype=jnp.float32)
    if prev.ndim != 1 or g.ndim != 1 or prev.shape != g.shape:
        raise ValueError(f"prev/g must be equal-length vectors, got "
                         f"{prev.shape} vs {g.shape}")
    return maxplus_conv_batched(prev[None], g[None], [band], block=block,
                                interpret=interpret)[0]


def maxplus_conv_np(prev: np.ndarray, g: np.ndarray,
                    band: Optional[int] = None) -> np.ndarray:
    """Float32 numpy oracle with the kernel's exact candidate arithmetic
    (f32 adds, order-free max) — the interpret-mode equivalence target."""
    prev32 = np.asarray(prev, dtype=np.float32)
    g32 = np.asarray(g, dtype=np.float32)
    n = prev32.shape[0] - 1
    b = n if band is None else max(0, min(int(band), n))
    pad = np.concatenate([np.full(b, NEG, dtype=np.float32), prev32])
    win = np.lib.stride_tricks.sliding_window_view(pad, b + 1)
    return (win + g32[b::-1][None, :]).max(axis=1)


# ---------------------------------------------------------------------------
# Grid-batched kernel: B independent banded convolutions, one pallas_call
# ---------------------------------------------------------------------------


def maxplus_conv_batched(prev, g, bands=None, *, block: int = 128,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Stacked banded max-plus convolution: ``prev`` and ``g`` are
    (B, n+1) float32 stacks, ``bands`` a per-row band sequence (``None``
    entries = dense; a scalar or ``None`` applies one band to every
    row).  Returns the (B, n+1) float32 value stack; row r equals
    ``maxplus_conv(prev[r], g[r], band=bands[r])`` — rows are padded to
    the widest band and the extra candidates are masked to -inf, which
    never beats the finite k=0 candidate.  The batch axis rides on the
    Pallas grid: one launch for the whole level of the batched
    PlanTable engine."""
    prev = jnp.asarray(prev, dtype=jnp.float32)
    g = jnp.asarray(g, dtype=jnp.float32)
    if prev.ndim != 2 or g.ndim != 2 or prev.shape != g.shape:
        raise ValueError(f"prev/g must be equal-shape (B, n+1) stacks, "
                         f"got {prev.shape} vs {g.shape}")
    B, n1 = prev.shape
    n = n1 - 1
    if bands is None or np.isscalar(bands):
        bands = [bands] * B
    bs = np.array([n if b is None else max(0, min(int(b), n))
                   for b in bands], dtype=np.int64)
    if len(bs) != B:
        raise ValueError(f"got {len(bs)} bands for a batch of {B}")
    bmax = int(bs.max()) if B else 0
    prev_pad = jnp.pad(prev, ((0, 0), (bmax, 0)), constant_values=NEG)
    ks = np.arange(n1)
    g = jnp.where(jnp.asarray(ks[None, :] > bs[:, None]), NEG, g)
    return _maxplus_rows(prev_pad, g, bmax, n1, block,
                         resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# Scan-compatible chunk kernel: the fused one-program engine's inner step
# ---------------------------------------------------------------------------


def maxplus_scan_chunk(wins, gs, *, block: int = 128,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Chunked max-plus step over pre-gathered windows — the fused
    planner engine's ``lax.scan`` inner kernel.

    ``wins`` is a (B, n1 + K - 1) stack of already-shifted ``prev``
    windows (position ``j + K-1-k`` holds ``prev[j - (off+k)]`` for the
    row's candidate-offset chunk base ``off``, -inf where out of range)
    and ``gs`` a (B, K) stack of reward-row chunks (masked to -inf past
    each row's band).  Returns the (B, n1) float32 stack::

        out[r, j] = max_{0 <= k < K} wins[r, j + K-1-k] + gs[r, k]

    Every shape is a function of (B, n1, K) only — all static per
    planner schedule signature — so one trace serves every scan step,
    and the fused engine's whole-table rebuild stays a single compiled
    dispatch.  Chunk decomposition is exact: a banded convolution's
    candidate set partitions over offset chunks, and the caller's
    scatter-max reduction over chunks reproduces the full-band maximum
    order-free."""
    wins = jnp.asarray(wins, dtype=jnp.float32)
    gs = jnp.asarray(gs, dtype=jnp.float32)
    if wins.ndim != 2 or gs.ndim != 2 or wins.shape[0] != gs.shape[0]:
        raise ValueError(f"wins/gs must be (B, n1+K-1)/(B, K) stacks, "
                         f"got {wins.shape} vs {gs.shape}")
    B, K = gs.shape
    n1 = wins.shape[1] - (K - 1)
    if n1 < 1:
        raise ValueError(f"window width {wins.shape[1]} shorter than "
                         f"chunk {K}")
    return _maxplus_rows(wins, gs, K - 1, n1, block,
                         resolve_interpret(interpret))
