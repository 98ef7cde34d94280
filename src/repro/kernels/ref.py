"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernel tests ``assert_allclose`` against
(and the backward function of the RMSNorm custom VJP).  They
intentionally share code with the model's own jnp paths so that switching
``kernel="jnp" -> "pallas"`` is a pure performance change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import blocked_attention, simple_attention
from repro.models.ssm import ssd_chunked


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0) -> jnp.ndarray:
    """Oracle attention: blocked online-softmax for long sequences,
    direct softmax for short ones (they agree to float tolerance).  Its
    matmuls run at full float32 precision, which a TPU otherwise replaces
    with bfloat16 passes."""
    attend = blocked_attention if q.shape[1] > 1024 else simple_attention
    with jax.default_matmul_precision("highest"):
        return attend(q, k, v, causal=causal, window=window,
                      softcap=softcap, q_offset=q_offset)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, init_state=None):
    """Oracle SSD chunk scan (see models/ssm.py)."""
    return ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk, init_state=init_state)


def rmsnorm(x, scale, *, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)
