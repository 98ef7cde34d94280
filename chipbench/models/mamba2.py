"""The ``mamba2`` model type: how a Mamba-2 configuration (the published
``config.json`` keys, with the Mamba2 layer's settings under
``mamba2_layer``) is handed to the program (``arch_config``), the
benchmark's weights for it from the seed (``params``), the plain
reference's loss (``seq_loss``), the model FLOPs of a training step
(``train_flops``) and the SSD forward kernel's operations and bytes
(``ssd_fwd``).

FLOP convention: 2 operations per multiply-add, the backward twice the
forward, nothing recomputed.  Counted are the projections, the tied head
(the embedding lookup is a gather) and the SSD scan in the chunked form
at the configuration's chunk L: per chunk and group the lower triangle
of C B^T, per head the lower triangle of the masked scores times x, the
chunk's state B^T x and the carried state's C h.  The depthwise conv,
norms, gates and the loss are left out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.ssm_lm import seq_loss  # noqa: F401

F32_BYTES = 4


def rows(cfg: dict) -> int:
    """Rows of the embedding and head: the vocabulary padded to
    ``pad_vocab_size_multiple``."""
    m = cfg["pad_vocab_size_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def widths(cfg: dict):
    """(d_model, d_inner, heads, head_dim, groups, d_state, d_conv)."""
    s = cfg["mamba2_layer"]
    d = cfg["d_model"]
    di = s["expand"] * d
    return (d, di, di // s["headdim"], s["headdim"], s["ngroups"],
            s["d_state"], s["d_conv"])


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, SSMConfig
    s = cfg["mamba2_layer"]
    if (cfg["ssm_cfg"].get("layer") != "Mamba2" or cfg["d_intermediate"]
            or cfg["attn_layer_idx"] or not cfg["rms_norm"]
            or not cfg["tie_embeddings"] or s["bias"] or not s["conv_bias"]
            or not s["rmsnorm"] or s["norm_before_gate"]
            or s["D_has_hdim"] or s["ngroups"] != 1):
        raise ValueError("the program's Mamba2 model is attention-free and "
                         "MLP-free with RMSNorm, a tied head, no projection "
                         "bias, a conv bias, one D a head, one B/C group "
                         "and the gated norm after the gate")
    return ArchConfig(
        name=cfg["name"], arch_type="ssm", source=cfg["source"],
        n_layers=cfg["n_layer"], d_model=cfg["d_model"], d_ff=0,
        vocab=rows(cfg),
        ssm=SSMConfig(d_state=s["d_state"], d_conv=s["d_conv"],
                      expand=s["expand"], head_dim=s["headdim"],
                      chunk=s["chunk_size"]),
        norm="rmsnorm", norm_eps=float(cfg["norm_epsilon"]),
        residual_in_fp32=cfg["residual_in_fp32"], tie_embeddings=True,
        param_dtype=cfg["param_dtype"])


def params(cfg: dict, key) -> dict:
    """Weights in the layer-stacked layout the program's model takes:
    projections normal with std 1/sqrt(fan-in) and the conv's with
    1/sqrt(d_conv), its bias zero, the embedding (tied to the head) with
    std 0.02, norm scales one, all in the configuration's type; per head,
    in float32, A_log = log U[A_init_range], dt_bias = softplus^-1 of a
    dt log-uniform on [dt_min, dt_max] floored at dt_init_floor, D one.

    The weights leave behind an optimization barrier: traced in one
    program with the optimizer's float32 master copy, XLA's excess
    precision would otherwise let the master skip the rounding to the
    configuration's type (it did on v5e), so that the program would start
    from other weights than the reference's."""
    dtype = jnp.dtype(cfg["param_dtype"])
    s = cfg["mamba2_layer"]
    d, di, H, _, G, N, K = widths(cfg)
    L = cfg["n_layer"]
    conv_ch = di + 2 * G * N
    ks = iter(jax.random.split(key, 8))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    dt = jnp.exp(jax.random.uniform(
        next(ks), (L, H), jnp.float32, math.log(s["dt_min"]),
        math.log(s["dt_max"])))
    dt = jnp.maximum(dt, s["dt_init_floor"])
    lo, hi = s["A_init_range"]
    layer = {
        "norm": {"scale": jnp.ones((L, d), dtype)},
        "mamba": {
            "w_in": normal((L, d, 2 * di + 2 * G * N + H), 1 / math.sqrt(d)),
            "conv_w": normal((L, K, conv_ch), 1 / math.sqrt(K)),
            "conv_b": jnp.zeros((L, conv_ch), dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (L, H), jnp.float32, lo, hi)),
            "D": jnp.ones((L, H), jnp.float32),
            "gate_norm": jnp.ones((L, di), dtype),
            "w_out": normal((L, di, d), 1 / math.sqrt(di)),
        },
    }
    return jax.lax.optimization_barrier(
        {"embed": {"w": normal((rows(cfg), d), 0.02)}, "segments": [[layer]],
         "final_norm": {"scale": jnp.ones(d, dtype)}})


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix multiplication per token: the layers'
    in- and out-projections and the tied head."""
    d, di, H, _, G, N, _ = widths(cfg)
    per_layer = d * (2 * di + 2 * G * N + H) + di * d
    return cfg["n_layer"] * per_layer + rows(cfg) * d


def ssd_token_flops(cfg: dict) -> float:
    """Forward operations of the SSD scan per token and layer at the
    configuration's chunk (module docstring)."""
    _, _, H, P, G, N, _ = widths(cfg)
    tri = (cfg["mamba2_layer"]["chunk_size"] + 1) / 2.0   # a chunk's mean row
    return 2.0 * (tri * G * N + tri * H * P + 2 * H * P * N)


def train_flops(cfg: dict, seq: int, sequences: int) -> float:
    """Model FLOPs of one training step: forward and backward, no
    recompute."""
    tokens = seq * sequences
    fwd = tokens * (2.0 * matmul_params(cfg)
                    + cfg["n_layer"] * ssd_token_flops(cfg))
    return 3.0 * fwd


def ssd_fwd(cfg: dict, batch: int, seq: int):
    """(operations, bytes) of one SSD forward kernel call over ``batch``
    sequences of ``seq`` tokens: its scan's operations, and one float32
    read of x, dt, A, B and C and one write of y and the final state."""
    _, _, H, P, G, N, _ = widths(cfg)
    ops = batch * seq * ssd_token_flops(cfg)
    elems = (batch * seq * (2 * H * P + H + 2 * G * N) + H
             + batch * H * P * N)
    return ops, float(elems * F32_BYTES)


def ssd_operands(cfg: dict, batch: int, seq: int):
    """The kernel's x and B operand shapes as a trace's op name writes
    them: x as (batch, heads, seq, head_dim), B and C as
    (batch, groups, seq, d_state), both float32."""
    _, _, H, P, G, N, _ = widths(cfg)
    return f"f32[{batch},{H},{seq},{P}]", f"f32[{batch},{G},{seq},{N}]"
