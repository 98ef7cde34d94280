"""Transformer / Mamba / MoE blocks with stacked-scan support.

Blocks are pre-norm residual units.  For every block kind we provide:
  init_block(key, cfg, kind, dtype)          -> param dict
  block_apply(params, cfg, kind, x, ...)     -> (x, aux_loss)
  block_cache_spec / block_decode             -> decode-path support

The model stacks `count` blocks of a kind by vmapping init and scanning
apply (see model.py); sliding-window patterns (gemma3 5:1) and the zamba2
shared block are handled by period-structured scans.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import (BLOCK_ATTN_DENSE, BLOCK_ATTN_MOE,
                                BLOCK_HYBRID_SHARED, BLOCK_MAMBA,
                                BLOCK_MLA_DENSE, BLOCK_MLA_MOE)
from repro.models import layers, mla, moe, ssm


def has_attn(kind: str) -> bool:
    return kind in (BLOCK_ATTN_DENSE, BLOCK_ATTN_MOE)


def has_mla(kind: str) -> bool:
    return kind in (BLOCK_MLA_DENSE, BLOCK_MLA_MOE)


def has_moe(kind: str) -> bool:
    return kind in (BLOCK_ATTN_MOE, BLOCK_MLA_MOE)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_block(key, cfg, kind: str, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {}
    if kind in (BLOCK_MAMBA, BLOCK_HYBRID_SHARED):
        p["norm"] = layers.init_norm(d, cfg.norm, dtype)
        p["mamba"] = ssm.init_mamba(ks[0], cfg, dtype)
        return p
    p["norm1"] = layers.init_norm(d, cfg.norm, dtype)
    p["norm2"] = layers.init_norm(d, cfg.norm, dtype)
    if has_mla(kind):
        p["attn"] = mla.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = layers.init_attention(ks[0], cfg, d, dtype)
    if has_moe(kind):
        p["moe"] = moe.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = layers.init_mlp(ks[1], d, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def init_shared_block(key, cfg, dtype) -> dict:
    """zamba2 weight-tied attention+MLP block."""
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "norm1": layers.init_norm(d, cfg.norm, dtype),
        "norm2": layers.init_norm(d, cfg.norm, dtype),
        "attn": layers.init_attention(ks[0], cfg, d, dtype),
        "mlp": layers.init_mlp(ks[1], d, cfg.d_ff, cfg.gated_mlp, dtype),
    }


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def pre_norm(p: dict, cfg, x):
    """A block's norm of the residual stream ``x``, computed in float32
    and handed on in the weights' type (a no-op cast unless the stream is
    float32, ``cfg.residual_in_fp32``)."""
    return layers.norm_apply(p, x, cfg.norm,
                             cfg.norm_eps).astype(p["scale"].dtype)


def block_apply(p: dict, cfg, kind: str, x, positions, *,
                layer_is_local: bool = False, kernel: str = "jnp"):
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (BLOCK_MAMBA, BLOCK_HYBRID_SHARED):
        h = pre_norm(p["norm"], cfg, x)
        x = x + ssm.mamba_apply(p["mamba"], cfg, h, kernel=kernel)
        return x, aux
    h = pre_norm(p["norm1"], cfg, x)
    if has_mla(kind):
        x = x + mla.mla_apply(p["attn"], cfg, h, positions, kernel=kernel)
    else:
        x = x + layers.attention_apply(p["attn"], cfg, h,
                                       layer_is_local=layer_is_local,
                                       positions=positions, kernel=kernel)
    h = pre_norm(p["norm2"], cfg, x)
    if has_moe(kind):
        y, aux = moe.moe_apply(p["moe"], cfg, h)
        x = x + y
    else:
        x = x + layers.mlp_apply(p["mlp"], h, cfg.mlp_act, cfg.gated_mlp)
    return x, aux


def shared_block_apply(p: dict, cfg, x, positions, kernel: str = "jnp"):
    h = pre_norm(p["norm1"], cfg, x)
    x = x + layers.attention_apply(p["attn"], cfg, h, layer_is_local=False,
                                   positions=positions, kernel=kernel)
    h = pre_norm(p["norm2"], cfg, x)
    x = x + layers.mlp_apply(p["mlp"], h, cfg.mlp_act, cfg.gated_mlp)
    return x


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def block_cache(cfg, kind: str, batch: int, capacity: int, dtype,
                layer_is_local: bool = False) -> dict:
    """Zero-initialized per-layer decode cache."""
    if kind in (BLOCK_MAMBA, BLOCK_HYBRID_SHARED):
        return ssm.mamba_init_state(cfg, batch, dtype)
    if has_mla(kind):
        return mla.mla_init_cache(cfg, batch, capacity, dtype)
    a = cfg.attn
    cap = min(capacity, a.window) if (layer_is_local and a.window) else capacity
    return {
        "k": jnp.zeros((batch, cap, a.n_kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, cap, a.n_kv_heads, a.head_dim), dtype),
    }


def block_decode(p: dict, cfg, kind: str, x, cache: dict, pos, *,
                 layer_is_local: bool = False):
    """One-token decode.  x: (B,1,d).  Returns (x, new_cache)."""
    if kind in (BLOCK_MAMBA, BLOCK_HYBRID_SHARED):
        h = pre_norm(p["norm"], cfg, x)
        y, new = ssm.mamba_decode(p["mamba"], cfg, h, cache)
        return x + y, new
    h = pre_norm(p["norm1"], cfg, x)
    if has_mla(kind):
        y, new = mla.mla_decode(p["attn"], cfg, h, cache, pos)
    else:
        y, nk, nv = layers.attention_decode(p["attn"], cfg, h, cache["k"],
                                            cache["v"], pos,
                                            layer_is_local=layer_is_local)
        new = {"k": nk, "v": nv}
    x = x + y
    h = pre_norm(p["norm2"], cfg, x)
    if has_moe(kind):
        y2, _ = moe.moe_apply(p["moe"], cfg, h)
        x = x + y2
    else:
        x = x + layers.mlp_apply(p["mlp"], h, cfg.mlp_act, cfg.gated_mlp)
    return x, new


def shared_block_decode(p: dict, cfg, x, cache: dict, pos):
    h = pre_norm(p["norm1"], cfg, x)
    y, nk, nv = layers.attention_decode(p["attn"], cfg, h, cache["k"],
                                        cache["v"], pos, layer_is_local=False)
    x = x + y
    h = pre_norm(p["norm2"], cfg, x)
    x = x + layers.mlp_apply(p["mlp"], h, cfg.mlp_act, cfg.gated_mlp)
    return x, {"k": nk, "v": nv}
