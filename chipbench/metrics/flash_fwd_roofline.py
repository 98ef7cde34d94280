"""Least time of the flash-attention forward kernel's calls (operations
and bytes from ``chipbench/flops.py``) over their device time in the
trace.  A call is a ``tpu_custom_call`` whose operands are the
configuration's q ``[mb, heads, seq, head_dim]`` and k/v
``[mb, kv_heads, seq, head_dim]`` tiles."""
from chipbench import flops


def read(run):
    if run.summary is None or run.peak is None:
        return None
    cfg, job = run.config, run.config["job"]
    mb = job["sequences_per_step"] // job["micro_batches"]
    S, D = job["seq_len"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q, kv = f"bf16[{mb},{H},{S},{D}]", f"bf16[{mb},{KV},{S},{D}]"
    secs, n = run.summary.ops(
        lambda name: "tpu_custom_call" in name and q in name and kv in name)
    if not n:
        return None
    ops, nbytes = flops.flash_fwd(mb, S, H, KV, D, causal=True)
    share, _ = flops.roofline_share(n * ops, n * nbytes, secs, run.peak)
    return share
