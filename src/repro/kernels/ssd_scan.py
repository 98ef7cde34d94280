"""Pallas TPU kernel for the Mamba2 SSD (state-space duality) chunk scan.

TPU adaptation of the SSD algorithm [arXiv:2405.21060]: instead of a
token-serial recurrence (hostile to the MXU), the sequence is processed in
chunks of L tokens.  Per chunk, everything is dense matmuls —

  intra-chunk:  Y_diag = ((C B^T) .* Lmat .* dt) X          (L,L)@(L,P)
  chunk state:  S_c    = (B .* decay .* dt)^T X             (N,L)@(L,P)
  inter-chunk:  Y_off  = exp(acum) .* (C S_{c-1})           (L,N)@(N,P)

— with the (P, N) recurrent state carried in VMEM scratch across the
chunk grid dimension (last grid dim = sequential on TPU).  The grid is
(batch, heads, chunks); blocks hold one chunk of one head: X (L, P),
dt as a (1, L) row, B/C (L, N) — all VMEM-resident, with L=chunk default
128 so the (L,L) and (L,N) matmuls are MXU-aligned and every block is
(8, 128)-aligned.  The per-head decay A is read from SMEM.

The backward kernel (``ssd_scan_bwd``) has the same blocks over a grid
(batch, heads, 2 * chunks): the first ``nc`` steps sweep the chunks
forward and keep each chunk's starting state in VMEM scratch, the last
``nc`` sweep them in reverse carrying the state's cotangent dS, so that
every cotangent of a chunk is again a handful of (L, .) matmuls.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_config import resolve_interpret


def _chunk_terms(dt, A, ic, *, chunk: int, seq: int):
    """What both kernels derive from a chunk's dt (a (1, L) row) and the
    head's decay A: dt with padding tokens zeroed (they contribute
    nothing), the mask ``tri`` of s <= l, the row<->column moves, and the
    inclusive prefix sum acum = cumsum(dt * A) as a column.

    Prefix sums and row<->column moves are masked (L, L) reductions:
    Mosaic lowers neither an in-kernel cumsum nor a transpose of an
    (L,)-vector, and a reduction over one nonzero term is exact, so the
    row and column copies of every vector agree bit for bit."""
    L = chunk
    tok = ic * L + lax.broadcasted_iota(jnp.int32, (1, L), 1)
    dt = jnp.where(tok < seq, dt, 0.0)
    row = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    eye = row == col
    tri = row >= col                                    # s <= l

    def to_col(v_row):                                  # (1, L) -> (L, 1)
        return jnp.sum(jnp.where(eye, v_row, 0.0), axis=1, keepdims=True)

    def to_row(v_col):                                  # (L, 1) -> (1, L)
        return jnp.sum(jnp.where(eye, v_col, 0.0), axis=0, keepdims=True)

    acum = jnp.sum(jnp.where(tri, dt * A, 0.0), axis=1,
                   keepdims=True)                       # (L, 1) inclusive
    return dt, tri, to_col, to_row, acum


def _head_major(v, pad: int):
    """(B, S, K, D) -> (B, K, S + pad, D), zero-padded to whole chunks."""
    return jnp.pad(v.transpose(0, 2, 1, 3),
                   ((0, 0), (0, 0), (0, pad), (0, 0)))


def _dt_rows(dt, pad: int):
    """(B, S, H) -> (B, H, 1, S + pad): one row of dt per head."""
    return jnp.pad(dt.transpose(0, 2, 1)[:, :, None],
                   ((0, 0), (0, 0), (0, 0), (0, pad)))


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref,
                state_ref, *, chunk: int, seq: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                 # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)               # (1, L) row
    A = a_ref[pl.program_id(1)]                         # SMEM scalar <= 0
    Bm = b_ref[0, 0].astype(jnp.float32)                # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                # (L, N)
    dt, tri, to_col, to_row, acum = _chunk_terms(dt, A, ic, chunk=chunk,
                                                 seq=seq)
    acum_row = to_row(acum)                             # (1, L)
    acum_last = acum[chunk - 1:chunk, :]                # (1, 1)

    # intra-chunk: Lmat[l, s] = exp(acum[l] - acum[s]) for s <= l
    lmat = jnp.where(tri, jnp.exp(acum - acum_row), 0.0)
    scores = lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (L, L)
    w = scores * lmat * dt
    y = lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)        # (L, P)

    # inter-chunk: contribution of the carried state (P, N)
    cs = lax.dot_general(Cm, state_ref[...],
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)       # (L, P)
    y = y + cs * jnp.exp(acum)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S = exp(acum[-1]) S + sum_s exp(acum[-1]-acum[s]) dt_s
    #                                         x_s B_s^T          (P, N)
    decay_out = jnp.exp(acum_last - acum) * to_col(dt)  # (L, 1)
    xb = lax.dot_general(x, Bm * decay_out,
                         (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)       # (P, N)
    state_ref[...] = state_ref[...] * jnp.exp(acum_last) + xb

    @pl.when(ic == nc - 1)
    def _emit_state():
        fin_ref[0, 0] = state_ref[...].astype(fin_ref.dtype)


def ssd_scan_fwd(x, dt, A, Bm, Cm, *, chunk: int = 128,
                 interpret: Optional[bool] = None):
    """x: (B,S,H,P) f32; dt: (B,S,H) f32; A: (H,) f32 (<=0);
    Bm, Cm: (B,S,G,N) with H % G == 0.
    Returns (y (B,S,H,P), final_state (B,H,P,N)).  ``interpret=None``
    defers to REPRO_PALLAS_INTERPRET / the backend default (compile only
    on TPU)."""
    interpret = resolve_interpret(interpret)
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0, (H, G)
    L = max(8, min(chunk, S))
    nc = pl.cdiv(S, L)
    pad = nc * L - S

    xt, dtt = _head_major(x, pad), _dt_rows(dt, pad)
    bt, ct = _head_major(Bm, pad), _head_major(Cm, pad)

    kernel = functools.partial(_ssd_kernel, chunk=L, seq=S)
    y, fin = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h * G // H, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h * G // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nc * L, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A, bt, ct)
    return y[:, :, :S].transpose(0, 2, 1, 3), fin


def _ssd_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, dfin_ref,
                    dx_ref, ddt_ref, dA_ref, db_ref, dc_ref,
                    states_ref, ds_ref, *, chunk: int, seq: int, nc: int):
    k = pl.program_id(2)
    ic = jnp.minimum(k, 2 * nc - 1 - k)                 # this step's chunk

    x = x_ref[0, 0, 0].astype(jnp.float32)              # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)               # (1, L) row
    A = a_ref[pl.program_id(1)]
    Bm = b_ref[0, 0, 0].astype(jnp.float32)             # (L, N)
    dt, tri, to_col, to_row, acum = _chunk_terms(dt, A, ic, chunk=chunk,
                                                 seq=seq)
    acum_last = acum[chunk - 1:chunk, :]                # (1, 1)
    decay_last = jnp.exp(acum_last)
    decay_out = jnp.exp(acum_last - acum) * to_col(dt)  # (L, 1)

    def dot(a, b, ca, cb):          # a and b contracted on axes ca and cb
        return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(k < nc)
    def _forward_sweep():
        # states_ref[c] is the state entering chunk c
        @pl.when(k == 0)
        def _init():
            states_ref[0] = jnp.zeros(states_ref.shape[1:], jnp.float32)

        @pl.when(k + 1 < nc)
        def _carry():
            states_ref[k + 1] = (states_ref[k] * decay_last
                                 + dot(x, Bm * decay_out, 0, 0))   # (P, N)

    @pl.when(k >= nc)
    def _reverse_sweep():
        @pl.when(k == nc)
        def _seed():
            ds_ref[...] = dfin_ref[0, 0].astype(jnp.float32)
            dA_ref[...] = jnp.zeros_like(dA_ref)

        dy = dy_ref[0, 0, 0].astype(jnp.float32)        # (L, P)
        Cm = c_ref[0, 0, 0].astype(jnp.float32)         # (L, N)
        s_prev = states_ref[ic]                         # (P, N)
        ds = ds_ref[...]                                # (P, N), dL/dS

        lmat = jnp.where(tri, jnp.exp(acum - to_row(acum)), 0.0)
        gl = dot(Cm, Bm, 1, 1) * lmat                   # (C B^T) .* Lmat
        dw = dot(dy, x, 1, 1)                           # dL/dW, W = gl .* dt
        r = dw * gl                                     # W's share of dL/ddt
        q = r * dt                                      # dL/dLmat .* Lmat
        dg = dw * lmat * dt                             # dL/d(C B^T)
        decay_in = jnp.exp(acum)                        # (L, 1)
        dy_s = dot(dy, s_prev, 1, 0)                    # (L, N)
        x_ds = dot(x, ds, 1, 0)                         # (L, N)

        dx_ref[0, 0, 0] = (dot(gl * dt, dy, 0, 0)
                           + decay_out * dot(Bm, ds, 1, 1)
                           ).astype(dx_ref.dtype)
        dc_ref[0, 0, 0] = (dot(dg, Bm, 1, 0)
                           + decay_in * dy_s).astype(dc_ref.dtype)
        db_ref[0, 0, 0] = (dot(dg, Cm, 0, 0)
                           + decay_out * x_ds).astype(db_ref.dtype)
        ds_ref[...] = ds * decay_last + dot(decay_in * dy, Cm, 0, 0)

        # dL/dacum: Lmat's two ends, the carried state's read
        # exp(acum) C S^T, and the state update's exp(acum[-1] - acum)
        e = jnp.sum(x_ds * Bm, axis=1, keepdims=True)   # dL/ddecay_out
        d_last = (decay_last * jnp.sum(jnp.sum(ds * s_prev, axis=1,
                                               keepdims=True),
                                       axis=0, keepdims=True)
                  + jnp.sum(decay_out * e, axis=0, keepdims=True))
        last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
        dacum = (jnp.sum(q, axis=1, keepdims=True)
                 - to_col(jnp.sum(q, axis=0, keepdims=True))
                 + decay_in * jnp.sum(Cm * dy_s, axis=1, keepdims=True)
                 - decay_out * e
                 + jnp.where(last, d_last, 0.0))        # (L, 1)
        # acum = cumsum(dt * A): da[s] = sum over l >= s of dacum[l]
        da = jnp.sum(jnp.where(tri, dacum, 0.0), axis=0,
                     keepdims=True)                     # (1, L)
        ddt = (jnp.sum(r, axis=0, keepdims=True)
               + to_row(jnp.exp(acum_last - acum) * e) + da * A)
        ddt_ref[0, 0] = ddt.astype(ddt_ref.dtype)
        dA_ref[0, 0] += da * dt


def ssd_scan_bwd(x, dt, A, Bm, Cm, dy, dfin, *, chunk: int = 128,
                 interpret: Optional[bool] = None):
    """Cotangents (dx, ddt, dA, dB, dC) of ``ssd_scan_fwd``'s inputs for
    the cotangents dy (B,S,H,P) of y and dfin (B,H,P,N) of the final
    state.  Operands and results are in the chunk-blocked layout
    (B, H or G, chunks, L, P or N); dB and dC are computed per head and
    summed over each group's heads here."""
    interpret = resolve_interpret(interpret)
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0, (H, G)
    L = max(8, min(chunk, S))
    nc = pl.cdiv(S, L)
    pad = nc * L - S

    def chunked(v):                     # (B, S, K, D) -> (B, K, nc, L, D)
        v = _head_major(v, pad)
        return v.reshape(Bsz, v.shape[1], nc, L, v.shape[3])

    def unchunked(v):                   # (B, K, nc, L, D) -> (B, S, K, D)
        return v.reshape(Bsz, v.shape[1], nc * L,
                         v.shape[4])[:, :, :S].transpose(0, 2, 1, 3)

    dtt = _dt_rows(dt, pad)

    # step k visits chunk min(k, 2nc-1-k): 0..nc-1, then nc-1..0.  The
    # operands and results only the reverse sweep uses stay on chunk
    # nc-1 through the forward sweep, so they are fetched once and
    # written back once.
    def both(k):
        return jnp.minimum(k, 2 * nc - 1 - k)

    def rev(k):
        return jnp.minimum(nc - 1, 2 * nc - 1 - k)

    def chunks(width, c, group=False):
        return pl.BlockSpec((1, 1, 1, L, width), lambda b, h, k: (
            b, h * G // H if group else h, c(k), 0, 0))

    def rows(c):
        return pl.BlockSpec((1, 1, 1, L), lambda b, h, k: (b, h, 0, c(k)))

    def per_head(shape):
        return pl.BlockSpec(shape, lambda b, h, k: (b, h, 0, 0))

    kernel = functools.partial(_ssd_bwd_kernel, chunk=L, seq=S, nc=nc)
    dx, ddt, dA, db, dc = pl.pallas_call(
        kernel,
        grid=(Bsz, H, 2 * nc),
        in_specs=[
            chunks(P, both), rows(both), pl.BlockSpec(memory_space=pltpu.SMEM),
            chunks(N, both, group=True), chunks(N, rev, group=True),
            chunks(P, rev), per_head((1, 1, P, N)),
        ],
        out_specs=[
            chunks(P, rev), rows(rev), per_head((1, 1, 1, L)),
            chunks(N, rev), chunks(N, rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nc, L, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, 1, nc * L), dt.dtype),
            jax.ShapeDtypeStruct((Bsz, H, 1, L), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, nc, L, N), Bm.dtype),
            jax.ShapeDtypeStruct((Bsz, H, nc, L, N), Cm.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((nc, P, N), jnp.float32),
                        pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(chunked(x), dtt, A, chunked(Bm), chunked(Cm), chunked(dy), dfin)

    def group_sum(v):                   # per-head partials -> (B, S, G, N)
        return unchunked(v.reshape(Bsz, G, H // G, nc, L, N).sum(2))

    return (unchunked(dx), ddt[:, :, 0, :S].transpose(0, 2, 1),
            dA.sum((0, 2, 3)).astype(A.dtype), group_sum(db), group_sum(dc))
