"""Mixture-of-experts FFN with TPU-idiomatic static-shape dispatch.

Tokens are routed top-k, sorted by expert id, and scattered into a fixed
(E, C, d) capacity buffer so expert matmuls are dense einsums with static
shapes (MXU-friendly; FLOPs ~= active FLOPs x capacity_factor).  Tokens
beyond an expert's capacity are dropped (standard GShard semantics); the
router aux loss keeps the load balanced.  Shared experts (DeepSeek) are
plain dense MLPs over all tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers

# Perf variant "moe3d" (EXPERIMENTS.md §Perf): dispatch into a 3-D
# (E, C+1, d) buffer whose expert dim is shardable over the model axis,
# instead of the flat (E*C+1, d) buffer (whose fused dim GSPMD cannot
# shard, forcing a replicated ~T*K*d materialization per device).
DISPATCH_3D = False

# Perf variant "moesm" (EXPERIMENTS.md §Perf): shard_map expert
# parallelism.  Under the (data..., model) mesh the activations are
# data-sharded and model-REPLICATED, so every model shard already holds
# all of its data shard's tokens: routing, sort, dispatch and combine can
# all be shard-LOCAL, each shard computes only its E/|model| experts, and
# the single collective left is a (T_local, d) psum of the combined
# output over the model axis — same traffic class as dense TP, instead
# of the (T*K, d) gather/scatter storms GSPMD emits for the global
# dispatch.  Set to (mesh, data_axes) by launch.dryrun.
SHARD_MAP = None


def capacity(n_tokens: int, n_experts: int, top_k: int,
             capacity_factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k * capacity_factor / n_experts))
    return max(8, -(-c // 8) * 8)                 # round up to multiple of 8


def init_moe(key, cfg, dtype) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "router": layers.init_dense(ks[0], d, m.n_experts, jnp.float32),
        "w_in": (jax.random.normal(ks[1], (m.n_experts, d, m.d_ff_expert),
                                   jnp.float32) * s).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (m.n_experts, d, m.d_ff_expert),
                                     jnp.float32) * s).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (m.n_experts, m.d_ff_expert, d),
                                    jnp.float32)
                  / math.sqrt(m.d_ff_expert)).astype(dtype),
    }
    if m.n_shared_experts:
        p["shared"] = layers.init_mlp(
            jax.random.fold_in(key, 7), d,
            m.n_shared_experts * m.d_ff_expert, True, dtype)
    return p


def moe_apply(p: dict, cfg, x: jnp.ndarray):
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar f32)."""
    if SHARD_MAP is not None:
        return moe_apply_shardmap(p, cfg, x)
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E, K = m.n_experts, m.top_k

    gate_logits = xt.astype(jnp.float32) @ p["router"]          # (T, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # (T, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # ---- load-balance aux loss (Switch-style) ----
    me = jnp.mean(probs, axis=0)                                # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1), axis=0)
    aux = jnp.sum(me * ce) * E * m.router_aux_weight

    # ---- sort-based dispatch into (E, C, d) ----
    C = capacity(T, E, K, m.capacity_factor)
    flat_e = top_e.reshape(T * K)                               # expert ids
    tok_of = jnp.repeat(jnp.arange(T), K)                       # token ids
    w_of = top_p.reshape(T * K)
    order = jnp.argsort(flat_e)                                 # stable
    se, st, sw = flat_e[order], tok_of[order], w_of[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E))             # (E,)
    pos = jnp.arange(T * K) - seg_start[se]                     # rank in expert
    keep = pos < C
    if DISPATCH_3D:
        # (E, C+1, d) scatter: column C is the trash slot for dropped
        # tokens; the E dim stays shardable over the model axis.
        posc = jnp.where(keep, pos, C)
        buf = jnp.zeros((E, C + 1, d), x.dtype).at[se, posc].set(xt[st])
        buf = buf[:, :C]
    else:
        slot = jnp.where(keep, se * C + pos, E * C)             # E*C = trash
        buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xt[st])
        buf = buf[:E * C].reshape(E, C, d)

    # ---- expert computation: dense per-expert matmuls ----
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h = jax.nn.silu(h) * g if cfg.mlp_act == "silu" \
        else jax.nn.gelu(h, approximate=True) * g
    yb = jnp.einsum("ecf,efd->ecd", h, p["w_out"])              # (E, C, d)

    # ---- combine back ----
    if DISPATCH_3D:
        posc = jnp.where(keep, pos, C)
        ybp = jnp.pad(yb, ((0, 0), (0, 1), (0, 0)))
        y_sorted = ybp[se, posc] * sw[:, None].astype(x.dtype)
    else:
        yb = jnp.concatenate([yb.reshape(E * C, d),
                              jnp.zeros((1, d), x.dtype)], axis=0)
        y_sorted = yb[jnp.where(keep, slot, E * C)] \
            * sw[:, None].astype(x.dtype)
    contrib = jnp.zeros((T, d), x.dtype).at[st].add(
        jnp.where(keep[:, None], y_sorted, 0))
    y = contrib

    if m.n_shared_experts:
        y = y + layers.mlp_apply(p["shared"], xt, cfg.mlp_act, True)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (perf variant "moesm")
# ---------------------------------------------------------------------------


def _moe_local(cfg, xt, router_w, w_in, w_gate, w_out, model_axis: str,
               data_axes, n_shards: int, shard_idx):
    """Per-device body: xt (T_l, d) local tokens (model-replicated);
    w_* hold the E_l = E/n_shards experts of this model shard.
    Returns (partial y (T_l, d) — psum'd over model by caller — and the
    local aux-loss sums)."""
    m = cfg.moe
    T, d = xt.shape
    E, K = m.n_experts, m.top_k
    E_l = E // n_shards

    gate_logits = xt.astype(jnp.float32) @ router_w             # (T_l, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    # aux-loss sufficient statistics (summed; caller normalizes globally)
    me_sum = jnp.sum(probs, axis=0)                             # (E,)
    ce_sum = jnp.sum(
        jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1))

    # keep only assignments routed to THIS shard's experts
    lo = shard_idx * E_l
    flat_e = top_e.reshape(T * K)
    flat_p = top_p.reshape(T * K)
    tok_of = jnp.repeat(jnp.arange(T), K)
    mine = (flat_e >= lo) & (flat_e < lo + E_l)
    local_e = jnp.where(mine, flat_e - lo, E_l)                 # E_l = trash
    C = capacity(T, E, K, m.capacity_factor)
    order = jnp.argsort(local_e)
    se, st, sw = local_e[order], tok_of[order], flat_p[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E_l + 1))
    pos = jnp.arange(T * K) - seg_start[jnp.minimum(se, E_l)]
    keep = (pos < C) & (se < E_l)
    posc = jnp.where(keep, pos, C)
    sec = jnp.minimum(se, E_l - 1)
    buf = jnp.zeros((E_l, C + 1, d), xt.dtype) \
        .at[jnp.where(keep, sec, 0), jnp.where(keep, posc, C)] \
        .set(jnp.where(keep[:, None], xt[st], 0))
    buf = buf[:, :C]

    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(h) * g if cfg.mlp_act == "silu" \
        else jax.nn.gelu(h, approximate=True) * g
    yb = jnp.einsum("ecf,efd->ecd", h, w_out)                   # (E_l, C, d)

    ybp = jnp.pad(yb, ((0, 0), (0, 1), (0, 0)))
    y_sorted = ybp[sec, posc] * sw[:, None].astype(xt.dtype)
    y = jnp.zeros((T, d), xt.dtype).at[st].add(
        jnp.where(keep[:, None], y_sorted, 0))
    return y, me_sum, ce_sum


def moe_apply_shardmap(p: dict, cfg, x: jnp.ndarray):
    """Expert-parallel MoE via shard_map (see SHARD_MAP above).

    The rest of the model is written for GSPMD propagation, so the
    shard_map runs over the mesh with Auto axis types: an Explicit mesh
    would type the output as data-sharded, and the backward of every
    later replicated-weight matmul would then contract over a sharded
    dimension, which explicit sharding refuses as ambiguous."""
    from jax.sharding import AxisType, Mesh, PartitionSpec as P

    mesh, data_axes = SHARD_MAP
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    m = cfg.moe
    model_axis = "model"
    n_shards = mesh.shape[model_axis]
    da = data_axes if len(data_axes) > 1 else data_axes[0]
    B, S, d = x.shape
    E = m.n_experts
    assert E % n_shards == 0, (E, n_shards)

    def body(x, router_w, w_in, w_gate, w_out):
        xt = x.reshape(-1, x.shape[-1])
        shard_idx = jax.lax.axis_index(model_axis)
        y, me_sum, ce_sum = _moe_local(cfg, xt, router_w, w_in, w_gate,
                                       w_out, model_axis, data_axes,
                                       n_shards, shard_idx)
        y = jax.lax.psum(y, model_axis)                  # combine experts
        me_sum = jax.lax.psum(me_sum, da)                # global aux stats
        ce_sum = jax.lax.psum(ce_sum, da)
        return y.reshape(x.shape), me_sum, ce_sum

    y, me_sum, ce_sum = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(da, None, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(da, None, None), P(None), P(None)),
    )(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])

    T_global = B * S
    me = me_sum / T_global
    ce = ce_sum / T_global
    aux = jnp.sum(me * ce) * E * m.router_aux_weight
    if m.n_shared_experts:
        y = y + layers.mlp_apply(p["shared"], x.reshape(-1, d),
                                 cfg.mlp_act, True).reshape(x.shape)
    return y, aux
