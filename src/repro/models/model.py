"""Unified model builder.

``build_model(cfg)`` returns a :class:`Model` bundle of pure functions:

  init(key)                          -> params
  forward(params, batch, ...)        -> (logits, extras)
  loss(params, batch, ...)           -> (scalar, metrics)
  init_cache(batch, capacity, dtype) -> decode caches
  decode_step(params, caches, tokens, pos) -> (logits, caches)

Layer stacks are executed as ``lax.scan`` over parameter pytrees stacked
along a leading ``count`` axis, so the lowered HLO is compact even for the
61-layer DeepSeek config.  Period-structured stacks (gemma3's 5-local:1-
global pattern, zamba2's shared block every 6 mamba layers) scan over
*periods* with the period slots unrolled in the body — locality is then
static per slot.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, BLOCK_HYBRID_SHARED, BLOCK_MLA_DENSE
from repro.models import blocks, layers

MTP_WEIGHT = 0.3

# Sequence-parallel TP (perf variant "seqpar", EXPERIMENTS.md §Perf):
# when set to a NamedSharding for the (B, S, d) residual stream with the
# sequence dim on the "model" axis, a sharding constraint is applied to
# the residual between blocks.  GSPMD then turns the per-layer TP
# all-reduces into reduce-scatter + all-gather pairs (Korthikanti et al.)
# and runs norms/elementwise on S/tp-sized shards.
SEQ_SHARDING = None


def _constrain(x):
    if SEQ_SHARDING is not None and x.ndim == 3:
        return jax.lax.with_sharding_constraint(x, SEQ_SHARDING)
    return x


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Segment:
    kind: str
    count: int                 # scan length (number of periods/groups)
    inner: int                 # layers per scan step
    locality: Tuple[bool, ...]  # per-slot sliding-window flag
    shared_after: bool = False  # zamba2: apply shared block after slots

    @property
    def n_layers(self) -> int:
        return self.count * self.inner


def segment_plan(cfg: ArchConfig) -> List[Segment]:
    segs: List[Segment] = []
    for kind, count in cfg.block_pattern:
        if count == 0:
            continue
        if kind == BLOCK_HYBRID_SHARED and cfg.shared_period:
            period = min(cfg.shared_period, count)
            groups, rem = divmod(count, period)
            if groups:
                segs.append(Segment(kind, groups, period,
                                    (False,) * period, shared_after=True))
            if rem:
                segs.append(Segment(kind, 1, rem, (False,) * rem))
            continue
        a = cfg.attn
        if a is not None and a.window and a.local_ratio[0] > 0:
            loc, glob = a.local_ratio
            period = loc + glob
            pattern = (True,) * loc + (False,) * glob
            if count < period:
                segs.append(Segment(kind, 1, count, pattern[:count]))
                continue
            groups, rem = divmod(count, period)
            segs.append(Segment(kind, groups, period, pattern))
            if rem:
                segs.append(Segment(kind, 1, rem, pattern[:rem]))
            continue
        segs.append(Segment(kind, count, 1, (False,)))
    return segs


# ---------------------------------------------------------------------------
# Model bundle
# ---------------------------------------------------------------------------


@dataclass
class Model:
    cfg: ArchConfig
    init: Callable
    forward: Callable
    loss: Callable
    init_cache: Callable
    decode_step: Callable
    segments: List[Segment]


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None):
    """Mean masked token cross-entropy.  logits f32 (..., V)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def build_model(cfg: ArchConfig) -> Model:
    dtype = jnp.dtype(cfg.param_dtype)
    segs = segment_plan(cfg)

    # ---------------- init ----------------

    def init(key) -> dict:
        keys = jax.random.split(key, len(segs) + 4)
        params: dict = {"embed": layers.init_embed(keys[0], cfg.vocab,
                                                   cfg.d_model, dtype)}
        seg_params = []
        for si, seg in enumerate(segs):
            slot_list = []
            for j in range(seg.inner):
                ks = jax.random.split(jax.random.fold_in(keys[1 + si], j),
                                      seg.count)
                slot_list.append(jax.vmap(
                    lambda k: blocks.init_block(k, cfg, seg.kind, dtype))(ks))
            seg_params.append(slot_list)
        params["segments"] = seg_params
        if cfg.shared_period:
            params["shared"] = blocks.init_shared_block(keys[-3], cfg, dtype)
        params["final_norm"] = layers.init_norm(cfg.d_model, cfg.norm, dtype)
        if not cfg.tie_embeddings:
            params["head"] = {"w": layers.init_dense(
                keys[-2], cfg.d_model, cfg.vocab, dtype).T}
        if cfg.mtp:
            params["mtp"] = {
                "block": blocks.init_block(keys[-1], cfg, BLOCK_MLA_DENSE
                                           if cfg.mla else segs[0].kind,
                                           dtype),
                "norm": layers.init_norm(cfg.d_model, cfg.norm, dtype),
            }
        return params

    def _residual(x):
        return x.astype(jnp.float32) if cfg.residual_in_fp32 else x

    def _head_w(params):
        return params["embed"]["w"] if cfg.tie_embeddings \
            else params["head"]["w"]

    # ---------------- embed inputs ----------------

    def _embed_inputs(params, batch):
        if cfg.modality == "audio_stub":
            return batch["frames"].astype(dtype)
        x = layers.embed_apply(params["embed"], batch["tokens"],
                               cfg.embed_scale, cfg.d_model)
        if cfg.modality == "vision_stub":
            pre = batch["prefix_embeds"].astype(x.dtype)
            x = jnp.concatenate([pre, x], axis=1)
        return _residual(x)

    # ---------------- forward ----------------

    def _run_segments(params, x, positions, kernel, remat):
        aux = jnp.zeros((), jnp.float32)
        for seg, slot_params in zip(segs, params["segments"]):
            shared_p = params.get("shared")

            def body(carry, xs, seg=seg, shared_p=shared_p):
                h, a = carry
                for j in range(seg.inner):
                    h, aj = blocks.block_apply(
                        xs[j], cfg, seg.kind, h, positions,
                        layer_is_local=seg.locality[j], kernel=kernel)
                    h = _constrain(h)
                    a = a + aj
                if seg.shared_after:
                    h = blocks.shared_block_apply(shared_p, cfg, h,
                                                  positions, kernel=kernel)
                    h = _constrain(h)
                return (h, a), None

            if remat:
                body = jax.checkpoint(body)
            (x, aux), _ = lax.scan(body, (x, aux), slot_params)
        return x, aux

    def forward(params, batch, *, kernel: str = "jnp", remat: bool = False,
                last_logits_only: bool = False):
        x = _embed_inputs(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        x, aux = _run_segments(params, x, positions, kernel, remat)
        h = blocks.pre_norm(params["final_norm"], cfg, x)
        if last_logits_only:
            # Serving prefill: only the last position's logits are needed
            # (full-seq logits at 32k x 262k vocab would be infeasible).
            logits = layers.logits_apply(_head_w(params), h[:, -1:])
            return logits, {"aux": aux}
        logits = layers.logits_apply(_head_w(params), h)
        extras = {"aux": aux}
        if cfg.mtp:
            hm, _ = blocks.block_apply(
                params["mtp"]["block"], cfg,
                BLOCK_MLA_DENSE if cfg.mla else segs[0].kind, x, positions)
            hm = blocks.pre_norm(params["mtp"]["norm"], cfg, hm)
            extras["mtp_logits"] = layers.logits_apply(_head_w(params), hm)
        return logits, extras

    # ---------------- loss ----------------

    def loss(params, batch, *, kernel: str = "jnp", remat: bool = False):
        logits, extras = forward(params, batch, kernel=kernel, remat=remat)
        metrics = {}
        if cfg.modality == "audio_stub":
            ce = cross_entropy(logits, batch["labels"],
                               batch.get("loss_mask"))
        else:
            toks = batch["tokens"]
            if cfg.modality == "vision_stub":
                logits = logits[:, -toks.shape[1]:]
            ce = cross_entropy(logits[:, :-1], toks[:, 1:],
                               None if batch.get("loss_mask") is None
                               else batch["loss_mask"][:, 1:])
        total = ce + extras["aux"]
        metrics["ce"] = ce
        metrics["aux"] = extras["aux"]
        if cfg.mtp and "mtp_logits" in extras:
            ml = extras["mtp_logits"]
            toks = batch["tokens"]
            if cfg.modality == "vision_stub":
                ml = ml[:, -toks.shape[1]:]
            mtp_ce = cross_entropy(ml[:, :-2], toks[:, 2:])
            total = total + MTP_WEIGHT * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        metrics["loss"] = total
        return total, metrics

    # ---------------- decode ----------------

    def init_cache(batch_size: int, capacity: int, cache_dtype=None):
        cdt = cache_dtype or dtype
        caches = []
        for seg in segs:
            slot_caches = []
            for j in range(seg.inner):
                one = blocks.block_cache(cfg, seg.kind, batch_size, capacity,
                                         cdt, layer_is_local=seg.locality[j])
                slot_caches.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (seg.count,) + a.shape)
                    .copy() if seg.count > 1 else a[None], one))
            entry = {"slots": slot_caches}
            if seg.shared_after:
                one = blocks.block_cache(cfg, "attn_dense", batch_size,
                                         capacity, cdt)
                entry["shared"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (seg.count,) + a.shape)
                    .copy() if seg.count > 1 else a[None], one)
            caches.append(entry)
        return caches

    def decode_step(params, caches, tokens, pos):
        """tokens: (B,) int32; pos: scalar int32 (absolute position).
        Returns (logits (B, vocab) f32, new_caches)."""
        x = _residual(layers.embed_apply(params["embed"], tokens[:, None],
                                         cfg.embed_scale, cfg.d_model))
        new_caches = []
        for seg, slot_params, cache in zip(segs, params["segments"], caches):
            shared_p = params.get("shared")

            def body(h, xs, seg=seg, shared_p=shared_p):
                sp, sc = xs
                new_sc = {"slots": []}
                for j in range(seg.inner):
                    h, nc = blocks.block_decode(
                        sp[j], cfg, seg.kind, h, sc["slots"][j], pos,
                        layer_is_local=seg.locality[j])
                    new_sc["slots"].append(nc)
                if seg.shared_after:
                    h, nsh = blocks.shared_block_decode(shared_p, cfg, h,
                                                        sc["shared"], pos)
                    new_sc["shared"] = nsh
                return h, new_sc

            x, new_cache = lax.scan(body, x, (slot_params, cache))
            new_caches.append(new_cache)
        h = blocks.pre_norm(params["final_norm"], cfg, x)
        logits = layers.logits_apply(_head_w(params), h)[:, 0]
        return logits, new_caches

    return Model(cfg=cfg, init=init, forward=forward, loss=loss,
                 init_cache=init_cache, decode_step=decode_step,
                 segments=segs)
