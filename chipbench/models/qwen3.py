"""The ``qwen3`` model type: how a qwen3-style configuration is handed to
the program (``arch_config``), the benchmark's weights for it from the
seed (``params``), the plain reference's loss (``seq_loss``) and the model
FLOPs of a training step (``train_flops``).  The training kind finds this
file by the configuration's ``model_type``; another model type is another
file beside it."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import flops
from chipbench.reference.dense_lm import seq_loss  # noqa: F401


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, AttnConfig
    if (cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu"
            or cfg["attention_bias"]):
        raise ValueError("the program's dense decoder has RMSNorm eps "
                         "1e-6, SiLU gating and no attention bias")
    return ArchConfig(
        name=cfg["name"], arch_type="dense", source=cfg["source"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        attn=AttnConfig(n_heads=cfg["num_attention_heads"],
                        n_kv_heads=cfg["num_key_value_heads"],
                        head_dim=cfg["head_dim"], qk_norm=True,
                        rope_theta=float(cfg["rope_theta"])),
        mlp_act="silu", norm="rmsnorm",
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"])


def params(cfg: dict, key) -> dict:
    """Weights in the layer-stacked layout the program's model takes, in
    the type the configuration states: projections normal with std
    1/sqrt(fan-in), the embedding (tied to the head) with std 0.02, norm
    scales one."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    L = cfg["num_hidden_layers"]
    ks = iter(jax.random.split(key, 8))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)  # noqa: E731
    layer = {
        "norm1": {"scale": ones(L, d)},
        "norm2": {"scale": ones(L, d)},
        "attn": {"wq": dense((L, d, h * hd), d),
                 "wk": dense((L, d, kv * hd), d),
                 "wv": dense((L, d, kv * hd), d),
                 "wo": dense((L, h * hd, d), h * hd),
                 "q_norm": ones(L, hd), "k_norm": ones(L, hd)},
        "mlp": {"w_in": dense((L, d, f), d), "w_out": dense((L, f, d), f),
                "w_gate": dense((L, d, f), d)},
    }
    embed = (jax.random.normal(next(ks), (cfg["vocab_size"], d), jnp.float32)
             * 0.02).astype(dtype)
    return {"embed": {"w": embed}, "segments": [[layer]],
            "final_norm": {"scale": ones(d)}}


def train_flops(cfg: dict, seq: int, sequences: int) -> float:
    """Model FLOPs of one training step: forward and backward, no
    recompute."""
    return 3.0 * flops.dense_forward_flops(cfg, seq, sequences)
