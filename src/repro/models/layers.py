"""Core model primitives: norms, RoPE, MLPs, attention.

Everything is functional: ``init_*`` builds a param dict, ``*_apply``
consumes it.  Attention is implemented *blocked* (flash-style online
softmax over KV blocks) so that prefill at 32k/524k sequence lengths never
materializes an (S, S) score matrix — this is both the memory-realistic
HLO for the dry-run and the jnp oracle for the Pallas kernel.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(d: int, kind: str, dtype) -> dict:
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


def norm_apply(p: dict, x: jnp.ndarray, kind: str, eps: float = 1e-6):
    if kind == "rmsnorm" and RMSNORM_FUSED:
        return rmsnorm_fused(x, p["scale"], eps)
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def rms_norm_weighted(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    """RMSNorm with an explicit scale vector (used for qk-norm, mamba gate)."""
    if RMSNORM_FUSED:
        return rmsnorm_fused(x, scale, eps)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def init_mlp(key, d_model: int, d_ff: int, gated: bool, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_in": init_dense(ks[0], d_model, d_ff, dtype),
         "w_out": init_dense(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["w_gate"] = init_dense(ks[2], d_model, d_ff, dtype)
    return p


def mlp_apply(p: dict, x: jnp.ndarray, act: str, gated: bool) -> jnp.ndarray:
    h = x @ p["w_in"]
    a = jax.nn.gelu(h, approximate=True) if act == "gelu" else jax.nn.silu(h)
    if gated:
        a = a * (x @ p["w_gate"])
    return a @ p["w_out"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., S, H, D) or (..., S, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs       # (..., S, half)
    if x.ndim == ang.ndim + 1:                                   # head dim present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, optional qk-norm, sliding window, softcap)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, d_model: int, dtype) -> dict:
    a = cfg.attn
    ks = jax.random.split(key, 6)
    p = {
        "wq": init_dense(ks[0], d_model, a.n_heads * a.head_dim, dtype),
        "wk": init_dense(ks[1], d_model, a.n_kv_heads * a.head_dim, dtype),
        "wv": init_dense(ks[2], d_model, a.n_kv_heads * a.head_dim, dtype),
        "wo": init_dense(ks[3], a.n_heads * a.head_dim, d_model, dtype),
    }
    if a.qk_norm:
        p["q_norm"] = jnp.ones((a.head_dim,), dtype)
        p["k_norm"] = jnp.ones((a.head_dim,), dtype)
    return p


def blocked_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                      q_block=512, kv_block=1024,
                      q_offset=None) -> jnp.ndarray:
    """Flash-style blocked attention (pure jnp oracle).

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D) with H % KV == 0.
    ``q_offset``: absolute position of q[:,0] (scalar int); defaults to
    Sk - Sq (decode-style right alignment).
    Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    G = H // KV
    if q_offset is None:
        q_offset = Sk - Sq

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    # pad to multiples
    nq = -(-Sq // q_block)
    nk = -(-Sk // kv_block)
    pq, pk = nq * q_block - Sq, nk * kv_block - Sk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    k_poss = jnp.where(jnp.arange(nk * kv_block) < Sk,
                       jnp.arange(nk * kv_block), jnp.iinfo(jnp.int32).max)

    qb = qp.reshape(B, nq, q_block, H, D).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_idx):
        qi, iq = qi_idx
        q_pos = q_offset + iq * q_block + jnp.arange(q_block)
        qg = qi.reshape(B, q_block, KV, G, D).astype(jnp.float32)

        def kv_step(carry, kj_idx):
            acc, m, l = carry
            kj, vj, jk = kj_idx
            kpos = lax.dynamic_slice_in_dim(k_poss, jk * kv_block, kv_block)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj.astype(jnp.float32))
            s = s / math.sqrt(D)
            if softcap and softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= kpos[None, :] <= q_pos[:, None]
            if window and window > 0:
                mask &= kpos[None, :] > q_pos[:, None] - window
            mask &= (kpos < jnp.iinfo(jnp.int32).max)[None, :]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard all-masked rows
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(s), 0.0, p)
            corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vj.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (acc_new, m_new, l_new), None

        kb = kp.reshape(B, nk, kv_block, KV, D).transpose(1, 0, 2, 3, 4)
        vb = vp.reshape(B, nk, kv_block, KV, Dv).transpose(1, 0, 2, 3, 4)
        acc0 = jnp.zeros((B, KV, G, q_block, Dv), jnp.float32)
        m0 = jnp.full((B, KV, G, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        (acc, m, l), _ = lax.scan(kv_step, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (B, KV, G, q_block, D) -> (B, q_block, H, D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, q_block, H, Dv)
        return None, out

    _, ob = lax.scan(q_step, None, (qb, jnp.arange(nq)))
    out = ob.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_block, H, Dv)[:, :Sq]
    return out.astype(q.dtype)


def simple_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                     q_offset=None) -> jnp.ndarray:
    """Unblocked reference attention (materializes full scores).  Used for
    small shapes and as a second-level oracle in tests."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    G = H // KV
    if q_offset is None:
        q_offset = Sk - Sq
    qg = q.reshape(B, Sq, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    s = s / math.sqrt(D)
    if softcap and softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window and window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


def attention_apply(p: dict, cfg, x: jnp.ndarray, *, layer_is_local: bool,
                    positions: jnp.ndarray, use_blocked: bool = True,
                    kernel: str = "jnp") -> jnp.ndarray:
    """Full-sequence (train / prefill) attention for one layer.

    x: (B, S, d_model); positions: (S,) absolute positions.
    ``layer_is_local`` selects the sliding-window mask for gemma3-style
    local layers.
    """
    a = cfg.attn
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, a.n_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(B, S, a.n_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(B, S, a.n_kv_heads, a.head_dim)
    if a.qk_norm:
        q = rms_norm_weighted(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_weighted(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions[None], a.rope_theta)
    k = apply_rope(k, positions[None], a.rope_theta)
    window = a.window if (a.window and layer_is_local) else 0
    if kernel == "pallas":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=a.causal, window=window,
                                 softcap=a.logit_softcap)
    elif kernel == "flash":
        from repro.models.flash_vjp import flash_attention_jnp
        o = flash_attention_jnp(q, k, v, a.causal, window,
                                a.logit_softcap, 0)
    elif use_blocked and S > 1024:
        o = blocked_attention(q, k, v, causal=a.causal, window=window,
                              softcap=a.logit_softcap, q_offset=0)
    else:
        o = simple_attention(q, k, v, causal=a.causal, window=window,
                             softcap=a.logit_softcap, q_offset=0)
    return o.reshape(B, S, a.n_heads * a.head_dim) @ p["wo"]


def attention_decode(p: dict, cfg, x: jnp.ndarray, cache_k, cache_v,
                     pos: jnp.ndarray, *, layer_is_local: bool):
    """One-token decode.  x: (B, 1, d); cache_k/v: (B, C, KV, D) where C is
    the cache capacity (full seq for global layers, window for local).
    ``pos``: int32 scalar or (B,) vector — absolute position of each
    lane's new token (per-lane positions enable continuous batching).

    For local (sliding-window) layers the cache is a ring buffer of size
    ``window``; for global layers a full-length buffer written at ``pos``.
    Returns (out (B,1,d), new_k, new_v).
    """
    a = cfg.attn
    B = x.shape[0]
    C = cache_k.shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q = (x @ p["wq"]).reshape(B, 1, a.n_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(B, 1, a.n_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(B, 1, a.n_kv_heads, a.head_dim)
    if a.qk_norm:
        q = rms_norm_weighted(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm_weighted(k, p["k_norm"], cfg.norm_eps)
    posv = pos_b[:, None]                                 # (B, 1)
    q = apply_rope(q, posv, a.rope_theta)
    k = apply_rope(k, posv, a.rope_theta)
    slot = jnp.where(jnp.array(layer_is_local and a.window > 0),
                     pos_b % jnp.maximum(C, 1),
                     jnp.minimum(pos_b, C - 1))           # (B,)
    lanes = jnp.arange(B)
    new_k = cache_k.at[lanes, slot].set(k[:, 0])
    new_v = cache_v.at[lanes, slot].set(v[:, 0])
    # validity mask over cache slots, per lane: (B, C)
    slots = jnp.arange(C)[None, :]
    posc = pos_b[:, None]
    if layer_is_local and a.window:
        valid = (slots <= posc % C) | (posc >= C)         # ring fill
        window_lo = posc - a.window
        abs_pos = jnp.where(slots <= posc % C, posc - (posc % C) + slots,
                            posc - (posc % C) + slots - C)
        valid &= (abs_pos > window_lo) & (abs_pos >= 0)
    else:
        valid = slots <= posc
    G = a.n_heads // a.n_kv_heads
    qg = q.reshape(B, 1, a.n_kv_heads, G, a.head_dim).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, new_k.astype(jnp.float32))
    s = s / math.sqrt(a.head_dim)
    if a.logit_softcap:
        s = jnp.tanh(s / a.logit_softcap) * a.logit_softcap
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, new_v.astype(jnp.float32))
    o = o.reshape(B, 1, a.n_heads * a.head_dim).astype(x.dtype)
    return o @ p["wo"], new_k, new_v


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def init_embed(key, vocab: int, d: int, dtype) -> dict:
    return {"w": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02)
            .astype(dtype)}


def embed_apply(p: dict, tokens: jnp.ndarray, scale: bool, d: int):
    x = jnp.take(p["w"], tokens, axis=0)
    if scale:
        x = x * jnp.asarray(math.sqrt(d), x.dtype)
    return x


def logits_apply(head_w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """head_w: (vocab, d) (tied layout); returns f32 logits."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      head_w.astype(jnp.float32))

# ---------------------------------------------------------------------------
# Fused RMSNorm (analytic custom VJP) — perf variant "fusednorm"
# ---------------------------------------------------------------------------
#
# Autodiff of the straightforward rmsnorm produces 5+ separate f32
# elementwise chains over (tokens, d_model) in the backward (see
# EXPERIMENTS.md §Perf iteration 2).  The analytic VJP below computes
#
#   r  = rsqrt(mean(x^2) + eps)
#   dx = r*gs - x * r^3 * mean(gs*x)          with gs = g * scale
#   dscale = sum(g * x * r)
#
# in one fused expression, saving nothing but (x, scale).  Exact same
# math as the autodiff path to float tolerance (tests/test_kernels.py).

RMSNORM_FUSED = False          # flipped by launch.dryrun variant "fusednorm"


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm_fused(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_fused_fwd(x, scale, eps):
    return rmsnorm_fused(x, scale, eps), (x, scale)


def _rmsnorm_fused_bwd(eps, res, g):
    x, scale = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    r = lax.rsqrt(ms + eps)
    gs = gf * scale.astype(jnp.float32)
    d = x.shape[-1]
    dot = jnp.sum(gs * xf, axis=-1, keepdims=True) / d
    dx = (r * gs - xf * (r ** 3) * dot).astype(x.dtype)
    dscale = jnp.sum((gf * xf * r).reshape(-1, d), axis=0)         .astype(scale.dtype)
    return dx, dscale


rmsnorm_fused.defvjp(_rmsnorm_fused_fwd, _rmsnorm_fused_bwd)
