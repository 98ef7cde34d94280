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


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, fin_ref,
                state_ref, *, chunk: int, seq: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    L = chunk
    x = x_ref[0, 0].astype(jnp.float32)                 # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)               # (1, L) row
    A = a_ref[pl.program_id(1)]                         # SMEM scalar <= 0
    Bm = b_ref[0, 0].astype(jnp.float32)                # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                # (L, N)

    # padding tokens contribute nothing: zero their dt
    tok = ic * L + lax.broadcasted_iota(jnp.int32, (1, L), 1)
    dt = jnp.where(tok < seq, dt, 0.0)

    # Prefix sums and row<->column moves as masked (L, L) reductions:
    # Mosaic lowers neither an in-kernel cumsum nor a transpose of an
    # (L,)-vector, and a reduction over one nonzero term is exact, so the
    # row and column copies of every vector agree bit for bit.
    row = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    eye = row == col
    tri = row >= col                                    # s <= l

    def to_col(v_row):                                  # (1, L) -> (L, 1)
        return jnp.sum(jnp.where(eye, v_row, 0.0), axis=1, keepdims=True)

    acum = jnp.sum(jnp.where(tri, dt * A, 0.0), axis=1,
                   keepdims=True)                       # (L, 1) inclusive
    acum_row = jnp.sum(jnp.where(eye, acum, 0.0), axis=0,
                       keepdims=True)                   # (1, L)
    acum_last = acum[L - 1:L, :]                        # (1, 1)

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

    xt = jnp.pad(x.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
    dtt = jnp.pad(dt.transpose(0, 2, 1)[:, :, None],
                  ((0, 0), (0, 0), (0, 0), (0, pad)))   # (B, H, 1, S) rows
    bt = jnp.pad(Bm.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
    ct = jnp.pad(Cm.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))

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
