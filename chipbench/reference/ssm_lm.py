"""Plain reference of a Mamba-2 language model, in float32 at ``highest``
matmul precision (``dense_lm.train_readings`` sets it).

It follows the published description (arXiv:2405.21060, the Mamba2
module of github.com/state-spaces/mamba): pre-norm blocks with RMSNorm on
a float32 residual stream; each mixer projects to (z, x, B, C, dt), runs
a causal depthwise conv with bias and SiLU over (x, B, C), takes
dt = softplus(dt + dt_bias) and A = -exp(A_log), scans the state
recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

position by position, applies the gated RMSNorm rmsnorm(y * silu(z)) and
projects out; a final RMSNorm and the head tied to the embedding give the
mean next-token cross-entropy.  B and C have ``ngroups`` groups, each
shared by heads / ngroups heads.  Nothing of the program under test is
imported, and the scan is the recurrence itself, not the chunked dual
form the program computes.  Every layer is recomputed in the backward
pass, and the scan keeps its state only at block boundaries, so that one
4096-token sequence fits beside the float32 state.

``dtype`` rounds the operands of every product (projections, conv, the
SSD's x, B and C, the head) as ``dense_lm.make_rounder`` does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.dense_lm import make_rounder, rms_norm

F32 = jnp.float32


def causal_conv(u, w, b):
    """Depthwise causal conv of one sequence; u: (S, C), w: (K, C) with
    w[K-1] on the current position."""
    K = w.shape[0]
    pad = jnp.pad(u, ((K - 1, 0), (0, 0)))
    return b + sum(pad[k:k + u.shape[0]] * w[k] for k in range(K))


def recurrence(x, dt, A, B, C, block: int = 64):
    """The SSM recurrence of one sequence.  x: (S, H, P); dt: (S, H);
    A: (H,); B, C: (S, G, N).  Returns y (S, H, P) without the D skip.
    The positions are scanned in blocks of ``block``, each recomputed in
    the backward pass."""
    S, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    Bh = jnp.repeat(B, H // G, axis=1)                  # (S, H, N)
    Ch = jnp.repeat(C, H // G, axis=1)
    decay = jnp.exp(dt * A)                             # (S, H)

    def one(h, t):
        a, d, xt, bt, ct = t
        h = a[:, None, None] * h + (d[:, None] * xt)[:, :, None] \
            * bt[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, ct)

    @jax.checkpoint
    def run_block(h, t):
        return lax.scan(one, h, t, unroll=8)

    block = min(block, S)
    nb = S // block
    if nb * block != S:
        raise ValueError(f"sequence {S} is not a multiple of {block}")
    seqs = [a.reshape((nb, block) + a.shape[1:])
            for a in (decay, dt, x, Bh, Ch)]
    _, y = lax.scan(run_block, jnp.zeros((H, P, N), F32), seqs)
    return y.reshape(S, H, P)


def mixer(p, u, cfg: dict, rnd):
    """The Mamba2 mixer of one sequence; u: (S, d) normalised input."""
    s = cfg["mamba2_layer"]
    eps = cfg["norm_epsilon"]
    S, d = u.shape
    di = s["expand"] * d
    P, G, N = s["headdim"], s["ngroups"], s["d_state"]
    H = di // P
    zxbcdt = rnd(u) @ rnd(p["w_in"])
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * G * N]
    dt = zxbcdt[:, 2 * di + 2 * G * N:]
    xbc = jax.nn.silu(causal_conv(rnd(xbc), rnd(p["conv_w"]), p["conv_b"]))
    x = rnd(xbc[:, :di]).reshape(S, H, P)
    B = rnd(xbc[:, di:di + G * N]).reshape(S, G, N)
    C = rnd(xbc[:, di + G * N:]).reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = recurrence(x, dt, A, B, C) + p["D"][:, None] * x
    g = rms_norm(y.reshape(S, di) * jax.nn.silu(z), p["gate_norm"], eps)
    return rnd(g) @ rnd(p["w_out"])


def seq_loss(params, tokens, cfg: dict, dtype: str = "float32"):
    """Mean next-token cross-entropy of one sequence ``tokens`` (S,)."""
    rnd = make_rounder(dtype)
    eps = cfg["norm_epsilon"]
    emb = params["embed"]["w"].astype(F32)
    lay = jax.tree.map(lambda a: a.astype(F32), params["segments"][0][0])

    @jax.checkpoint
    def layer(x, p):
        h = rms_norm(x, p["norm"]["scale"], eps)
        return x + mixer(p["mamba"], h, cfg, rnd), None

    x, _ = lax.scan(layer, emb[tokens], lay)
    h = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    logits = rnd(h) @ rnd(emb).T
    logz = jax.nn.logsumexp(logits[:-1], axis=-1)
    picked = jnp.take_along_axis(logits[:-1], tokens[1:, None], -1)[:, 0]
    return jnp.mean(logz - picked)
