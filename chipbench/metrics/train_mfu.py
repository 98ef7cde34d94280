"""Model FLOPs of the committed steps (forward and backward, no
recompute, from ``chipbench/flops.py``) over the window and the chips'
bf16 peak."""


def read(run):
    c = run.counters
    if not c.get("steps") or run.peak is None:
        return None
    achieved = c["train_flops_per_step"] * c["steps"] / run.window_s
    return 100.0 * achieved / (run.peak["bf16_flops_per_s"] * c["chips"])
