"""Share of the window the managed loop (``launch/train.py`` ``run``)
spends outside the benchmark's spans around the step, the SEV2
iteration, the snapshot and the restore."""
from chipbench.harness import covered

TOP = ("train.step", "sev2.iteration", "ckpt.snapshot", "ckpt.restore")


def read(run):
    lo, hi = run.window
    inside = [(s, e) for n, s, e in run.spans.records
              if n in TOP and s >= lo and e <= hi + 1e-9]
    if not inside or hi <= lo:
        return None
    return 100.0 * (1.0 - covered(inside) / (hi - lo))
