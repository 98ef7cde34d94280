"""Operations and bytes the algorithms need, from shapes alone.

Model FLOPs count the forward and backward passes once (no recompute):
2 operations per multiply-add, the backward twice the forward.  Causal
attention counts the lower triangle only.  Kernel counts are the least
the kernel must do: its useful multiply-adds and one read of each input
and one write of each output.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def dense_matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix multiplication per token: the layers'
    projections and MLPs, and the output head (the embedding lookup is a
    gather)."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def attention_fwd_flops(seq: int, heads: int, head_dim: int,
                        causal: bool = True) -> float:
    """QK^T and PV of one sequence in one layer, forward only."""
    full = 2.0 * 2.0 * seq * seq * heads * head_dim
    return full / 2.0 if causal else full


def dense_forward_flops(cfg: dict, seq: int, sequences: int,
                        causal: bool = True) -> float:
    tokens = seq * sequences
    attn = attention_fwd_flops(seq, cfg["num_attention_heads"],
                               cfg["head_dim"], causal)
    return (2.0 * dense_matmul_params(cfg) * tokens
            + cfg["num_hidden_layers"] * sequences * attn)


def flash_fwd(batch: int, seq: int, heads: int, kv_heads: int,
              head_dim: int, causal: bool = True,
              itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one flash-attention forward call:
    q, k, v read once and the output written once."""
    ops = batch * attention_fwd_flops(seq, heads, head_dim, causal)
    elems = batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return ops, float(elems * itemsize)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> Tuple[float, str]:
    """(least time / measured time in %, which bound sets the least)."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
