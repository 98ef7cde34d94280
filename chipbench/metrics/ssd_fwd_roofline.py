"""Least time of the Mamba-2 SSD forward kernel's calls (operations and
bytes from ``chipbench/models/mamba2.py``) over their device time in the
trace.  A call is a ``tpu_custom_call`` whose operands are the
configuration's x ``[mb, heads, seq, head_dim]`` and B/C
``[mb, groups, seq, d_state]`` tiles, in float32."""
from chipbench import flops, harness


def read(run):
    if run.summary is None or run.peak is None:
        return None
    cfg, job = run.config, run.config["job"]
    model = harness.load_module("models", cfg["model_type"])
    mb = job["sequences_per_step"] // job["micro_batches"]
    S = job["seq_len"]
    x, bc = model.ssd_operands(cfg, mb, S)
    secs, n = run.summary.ops(
        lambda name: "tpu_custom_call" in name and x in name and bc in name)
    if not n:
        return None
    ops, nbytes = model.ssd_fwd(cfg, mb, S)
    share, _ = flops.roofline_share(n * ops, n * nbytes, secs, run.peak)
    return share
