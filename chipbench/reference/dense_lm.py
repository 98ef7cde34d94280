"""Plain reference of a qwen3-style dense decoder and its AdamW training
steps, in float32 at ``highest`` matmul precision.

It follows the published description: pre-norm blocks with RMSNorm,
grouped-query attention with per-head RMSNorm on q and k, rotary
embeddings over the two halves of each head, causal softmax, a SwiGLU
MLP, a final RMSNorm and the output head tied to the embedding; the loss
is the mean next-token cross-entropy.  AdamW clips the global gradient
norm, applies decoupled weight decay to every weight and follows a cosine
schedule with linear warm-up.  Nothing of the program under test is
imported.  Attention runs one block of queries at a time, recomputed in
the backward pass, so that one 4096-token sequence fits beside the
float32 state.

``dtype`` sets the precision that every matrix product's operands are
rounded to (``"float32"`` is the reference; a narrower type, with
per-tensor scaling for the 8-bit ones, is the control).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = {"float8_e4m3fn": 448.0, "float8_e5m2": 57344.0}


def _round_to(x, dtype: str):
    if dtype == "float32":
        return x
    if dtype in FP8_MAX:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX[dtype]
        return (x / scale).astype(dtype).astype(F32) * scale
    return x.astype(dtype).astype(F32)


def make_rounder(dtype: str):
    """Rounding of a product's operand, and of its cotangent in the
    backward pass, to ``dtype``."""
    if dtype == "float32":
        return lambda x: x

    @jax.custom_vjp
    def rnd(x):
        return _round_to(x, dtype)

    rnd.defvjp(lambda x: (_round_to(x, dtype), None),
               lambda _, g: (_round_to(g, dtype),))
    return rnd


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x: (S, heads, D); rotates the first half against the second."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, rnd, block: int):
    """Causal GQA attention of one sequence; q: (S, H, D), k, v:
    (S, KV, D).  One block of ``block`` queries at a time."""
    S, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    block = min(block, S)
    nb = S // block
    qb = q.reshape(nb, block, KV, G, D)
    k, v = rnd(k), rnd(v)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", rnd(qi), k) / math.sqrt(D)
        qpos = i * block + jnp.arange(block)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", rnd(p), v)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return out.reshape(S, H, D)


def seq_loss(params, tokens, cfg: dict, dtype: str = "float32",
             block: int = 512):
    """Mean next-token cross-entropy of one sequence ``tokens`` (S,)."""
    rnd = make_rounder(dtype)

    def mm(a, b):
        return rnd(a) @ rnd(b)

    eps = cfg["rms_norm_eps"]
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    S = tokens.shape[0]
    emb = params["embed"]["w"].astype(F32)
    x = emb[tokens]
    lay = params["segments"][0][0]
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[i].astype(F32), lay)
        h = rms_norm(x, p["norm1"]["scale"], eps)
        q = mm(h, p["attn"]["wq"]).reshape(S, H, D)
        k = mm(h, p["attn"]["wk"]).reshape(S, KV, D)
        v = mm(h, p["attn"]["wv"]).reshape(S, KV, D)
        q = rope(rms_norm(q, p["attn"]["q_norm"], eps), cfg["rope_theta"])
        k = rope(rms_norm(k, p["attn"]["k_norm"], eps), cfg["rope_theta"])
        o = attention(q, k, v, rnd, block).reshape(S, H * D)
        x = x + mm(o, p["attn"]["wo"])
        h = rms_norm(x, p["norm2"]["scale"], eps)
        m = p["mlp"]
        x = x + mm(jax.nn.silu(mm(h, m["w_in"])) * mm(h, m["w_gate"]),
                   m["w_out"])
    h = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    logits = mm(h, emb.T)
    logz = jax.nn.logsumexp(logits[:-1], axis=-1)
    picked = jnp.take_along_axis(logits[:-1], tokens[1:, None], -1)[:, 0]
    return jnp.mean(logz - picked)


def lr_at(step: int, job: dict) -> float:
    """Cosine schedule with linear warm-up, at 1-based optimizer step."""
    peak, warm, total = job["lr"], job["warmup_steps"], job["total_steps"]
    ratio = job["adamw"]["min_lr_ratio"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (ratio + (1 - ratio) * 0.5 * (1 + math.cos(math.pi * prog)))


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
         for x in jax.tree.leaves(t)]))(tree), np.float64)


def train_readings(params0, batches: Sequence, cfg: dict, job: dict,
                   dtype: str = "float32",
                   rows: Optional[Sequence[Sequence[int]]] = None,
                   loss=None) -> Dict[str, object]:
    """Three AdamW steps from ``params0`` on ``batches`` (one (n, S)
    token array a step), one sequence a micro-batch.  ``rows[i]``, when
    given, are the rows of step i that enter its gradient (a planted
    fault leaves some out).  Returns the loss and pre-clip gradient norm
    of each step, the per-leaf norms of the first gradient and of the
    weights' change after the three steps.  ``loss`` is the model type's
    ``seq_loss`` (this module's dense decoder by default)."""
    hp = job["adamw"]
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(functools.partial(
            loss or seq_loss, cfg=cfg, dtype=dtype)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

        @jax.jit
        def adamw(p, g, m, v, t, lr):
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gn, 1e-12))
            g = jax.tree.map(lambda x: x * scale, g)
            m = jax.tree.map(lambda a, b: hp["b1"] * a + (1 - hp["b1"]) * b,
                             m, g)
            v = jax.tree.map(
                lambda a, b: hp["b2"] * a + (1 - hp["b2"]) * b * b, v, g)
            c1, c2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t
            p = jax.tree.map(
                lambda x, a, b: x - lr * ((a / c1) / (jnp.sqrt(b / c2)
                                                      + hp["eps"])
                                          + hp["weight_decay"] * x),
                p, m, v)
            return p, m, v, gn

        p = jax.tree.map(lambda x: x.astype(F32), params0)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, gnorms, first = [], [], None
        for i, toks in enumerate(batches):
            use = (list(range(toks.shape[0])) if rows is None or rows[i] is None
                   else rows[i])
            g, total = None, 0.0
            for r in use:
                loss, gi = vg(p, toks[r])
                total += float(loss)
                g = gi if g is None else add(g, gi)
            g = jax.tree.map(lambda x: x / len(use), g)
            losses.append(total / len(use))
            if first is None:
                first = leaf_norms(g)
            p, m, v, gn = adamw(p, g, m, v, float(i + 1),
                                lr_at(i + 1, job))
            gnorms.append(float(gn))
            del g
        change = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: x - y.astype(F32), a, b))(p, params0)
        out = {"loss": losses, "gnorm": gnorms, "grad_leaf": first,
               "change_leaf": leaf_norms(change)}
        del p, m, v, change
    return out


def leaf_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or the median leaf's,
    whichever is larger.  Leaves whose reference gradient is nought to
    rounding (under a thousandth of the median) are left out by the
    caller."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref))
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, med)))


def compare(prog: Dict[str, object], ref: Dict[str, object]
            ) -> Dict[str, float]:
    """The numbers compared: worst relative loss gap over the steps whose
    loss the program reports, worst relative gap of the pre-clip gradient
    norm, and the worst-leaf gaps of the first gradient and of the
    weights' change after three steps."""
    keep = np.asarray(ref["grad_leaf"]) >= 1e-3 * float(
        np.median(ref["grad_leaf"]))
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])
            if a is not None]
    gn = [abs(a - b) / abs(b) for a, b in zip(prog["gnorm"], ref["gnorm"])]
    return {
        "loss_gap": max(loss) if loss else float("inf"),
        "gnorm_gap": max(gn),
        "grad_leaf_gap": leaf_gap(np.asarray(prog["grad_leaf"])[keep],
                                  np.asarray(ref["grad_leaf"])[keep]),
        "change_leaf_gap": leaf_gap(np.asarray(prog["change_leaf"])[keep],
                                    np.asarray(ref["change_leaf"])[keep]),
    }


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
