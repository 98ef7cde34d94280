"""Jit'd public wrappers around the Pallas kernels.

Each op runs the Pallas forward kernel under a ``jax.custom_vjp``.
Attention and RMSNorm differentiate pure-jnp code that agrees with the
kernel output to float tolerance (asserted by tests/test_kernels.py).
Attention recomputes through the flash-style custom VJP of
``models.flash_vjp``, so its backward keeps nothing of size O(S^2);
differentiating the blocked oracle would stack every block's
probabilities (6 GB a layer at 32 heads x 4096 tokens).  RMSNorm
differentiates its oracle in ``ref.py``.  The SSD scan has a Pallas
backward kernel of its own (``ssd_scan.ssd_scan_bwd``); its oracle in
``ref.py`` is the tests' reference for both directions.

``interpret`` resolution lives in ``pallas_config.resolve_interpret``: the
kernels compile on TPU (Mosaic) and interpret everywhere else, with
REPRO_PALLAS_INTERPRET / per-call kwargs as the overrides.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_scan_bwd, ssd_scan_fwd
from repro.models.flash_vjp import flash_attention_jnp


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0) -> jnp.ndarray:
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset)


def _fa_fwd(q, k, v, causal, window, softcap, q_offset):
    out = flash_attention(q, k, v, causal, window, softcap, q_offset)
    return out, (q, k, v)


def _fa_bwd(causal, window, softcap, q_offset, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_jnp(q, k, v, causal, window,
                                            softcap, q_offset), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 128) -> Tuple:
    return ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=chunk)


def _ssd_fwd(x, dt, A, Bm, Cm, chunk):
    out = ssd_scan(x, dt, A, Bm, Cm, chunk)
    return out, (x, dt, A, Bm, Cm)


def _ssd_bwd(chunk, res, g):
    dy, dfin = g
    return ssd_scan_bwd(*res, dy, dfin, chunk=chunk)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


# ---------------------------------------------------------------------------
# fused RMSNorm
# ---------------------------------------------------------------------------


@jax.custom_vjp
def rmsnorm(x, scale) -> jnp.ndarray:
    return rmsnorm_fwd(x, scale)


def _rn_fwd(x, scale):
    return rmsnorm(x, scale), (x, scale)


def _rn_bwd(res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x, s: ref.rmsnorm(x, s), x, scale)
    return vjp(g)


rmsnorm.defvjp(_rn_fwd, _rn_bwd)
