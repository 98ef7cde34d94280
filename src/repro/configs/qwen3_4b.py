"""qwen3-4b [dense] — qk_norm, GQA.  [hf:Qwen/Qwen3-4B]"""
import dataclasses

from repro.configs.base import ArchConfig, AttnConfig, register

ARCH = register(ArchConfig(
    name="qwen3-4b",
    arch_type="dense",
    source="hf:Qwen/Qwen3-4B",
    n_layers=36,
    d_model=2560,
    d_ff=9728,
    vocab=151936,
    attn=AttnConfig(n_heads=32, n_kv_heads=8, head_dim=128,
                    qk_norm=True, rope_theta=1_000_000.0),
    mlp_act="silu",
    norm="rmsnorm",
))

#: One TPU v5e chip's share of a deployment in which four chips share
#: every layer's vocabulary (embedding and head rows) and the 36 layers lie
#: on further chips as pipeline stages.  Every width is as published.
ONE_CHIP_CUT = {
    "n_layers": 2,              # of 36 (one period of the uniform stack)
    "vocab": 151936 // 4,       # 37,984 rows: a quarter of the vocabulary
}


def one_chip_share() -> ArchConfig:
    """``ARCH`` cut to ``ONE_CHIP_CUT`` (~0.4 B params, bf16 weights)."""
    return dataclasses.replace(ARCH, **ONE_CHIP_CUT)
