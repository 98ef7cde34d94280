"""DeepSeek-V3 Multi-head Latent Attention.  [arXiv:2412.19437]

Prefill materializes K/V from the compressed latent; decode uses the
*absorbed* formulation — the KV cache holds only the (kv_lora_rank +
qk_rope_head_dim) latent per token, and W_uk / W_uv are folded into the
query/output paths.  This is the memory-optimal TPU mapping of MLA.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers


def init_mla(key, cfg, dtype) -> dict:
    m = cfg.mla
    d = cfg.d_model
    H = cfg.attn.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "w_dq": layers.init_dense(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "w_uq": layers.init_dense(ks[1], m.q_lora_rank, H * (dn + dr), dtype),
        "w_dkv": layers.init_dense(ks[2], d, m.kv_lora_rank + dr, dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_uk": layers.init_dense(ks[3], m.kv_lora_rank, H * dn, dtype),
        "w_uv": layers.init_dense(ks[4], m.kv_lora_rank, H * dv, dtype),
        "wo": layers.init_dense(ks[5], H * dv, d, dtype),
    }


def _project_q(p, cfg, x, positions):
    m = cfg.mla
    H = cfg.attn.n_heads
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    B, S, _ = x.shape
    cq = layers.rms_norm_weighted(x @ p["w_dq"], p["q_norm"],
                                  cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.attn.rope_theta)
    return q_nope, q_rope


def _project_kv_latent(p, cfg, x, positions):
    m = cfg.mla
    dr = m.qk_rope_head_dim
    ckv_full = x @ p["w_dkv"]
    ckv = layers.rms_norm_weighted(ckv_full[..., :m.kv_lora_rank],
                                   p["kv_norm"], cfg.norm_eps)
    k_rope = layers.apply_rope(ckv_full[..., m.kv_lora_rank:], positions,
                               cfg.attn.rope_theta)          # (B,S,dr)
    return ckv, k_rope


def mla_apply(p: dict, cfg, x: jnp.ndarray, positions: jnp.ndarray,
              use_blocked: bool = True, kernel: str = "jnp") -> jnp.ndarray:
    """Full-sequence (train / prefill).  x: (B,S,d)."""
    m = cfg.mla
    H = cfg.attn.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B, S, _ = x.shape
    pos = positions[None]

    q_nope, q_rope = _project_q(p, cfg, x, pos)
    ckv, k_rope = _project_kv_latent(p, cfg, x, pos)
    k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, dn)
    v = (ckv @ p["w_uv"]).reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, dr))], axis=-1)
    if kernel == "flash":
        from repro.models.flash_vjp import flash_attention_jnp
        o = flash_attention_jnp(q, k, v, True, 0, 0.0, 0)
    elif use_blocked and S > 1024:
        o = layers.blocked_attention(q, k, v, causal=True, q_offset=0)
    else:
        o = layers.simple_attention(q, k, v, causal=True, q_offset=0)
    return o.reshape(B, S, H * dv) @ p["wo"]


def mla_init_cache(cfg, batch: int, capacity: int, dtype) -> dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
    }


def mla_decode(p: dict, cfg, x: jnp.ndarray, cache: dict, pos):
    """Absorbed one-token decode.  x: (B,1,d); cache latent buffers."""
    m = cfg.mla
    H = cfg.attn.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B = x.shape[0]
    C = cache["ckv"].shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    posv = pos_b[:, None]                                 # (B, 1)

    q_nope, q_rope = _project_q(p, cfg, x, posv)          # (B,1,H,dn/(dr))
    ckv_t, k_rope_t = _project_kv_latent(p, cfg, x, posv)  # (B,1,rank),(B,1,dr)
    lanes = jnp.arange(B)
    new_ckv = cache["ckv"].at[lanes, pos_b].set(ckv_t[:, 0])
    new_krope = cache["k_rope"].at[lanes, pos_b].set(k_rope_t[:, 0])

    # absorb W_uk into q:  q_lat (B,1,H,rank)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, dn)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, new_ckv.astype(jnp.float32))
         + jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                      new_krope.astype(jnp.float32)))
    s = s / math.sqrt(dn + dr)
    valid = jnp.arange(C)[None, :] <= pos_b[:, None]      # (B, C)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    ctx = jnp.einsum("bhqs,bsr->bqhr", w, new_ckv.astype(jnp.float32))
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, dv)
    o = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv.astype(jnp.float32))
    o = o.reshape(B, 1, H * dv).astype(x.dtype)
    return o @ p["wo"], {"ckv": new_ckv, "k_rope": new_krope}
