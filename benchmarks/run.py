"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run detection  # one
    python benchmarks/run.py --quick                   # CI smoke subset
    python benchmarks/run.py --only planner_scale      # one, full grid

``--quick`` sets REPRO_BENCH_QUICK=1 (benches trim their grids) and runs
the smoke subset unless specific benches are named.

``--only <bench>`` (repeatable; ``--only=<bench>`` also accepted) names
a single bench the same way a positional name does — use it to
re-record one baseline after a model change that only moves that
bench's rows, e.g. ``python benchmarks/run.py --only maxplus`` after a
kernel change, instead of regenerating the whole ``results/`` suite.
Baselines land wherever ``REPRO_RESULTS`` points (default
``results/``); commit the refreshed JSON so the CI regression gate
(``benchmarks/check_regression.py``) compares against it.
"""
from __future__ import annotations

import os
import sys
import time

# allow `python benchmarks/run.py` from a bare checkout: put the repo root
# (for the `benchmarks` package) and src/ (for `repro`) on the path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCHES = ["detection", "costmodel", "maxplus", "planner_scale",
           "cluster_sim", "serving_slo", "transition", "frontier",
           "throughput", "waf_multitask", "traces", "ablation",
           "chaos", "controlplane"]
QUICK_BENCHES = ["detection", "costmodel", "maxplus", "planner_scale",
                 "cluster_sim", "serving_slo", "transition", "frontier",
                 "chaos", "controlplane"]


def main() -> None:
    args = sys.argv[1:]
    quick = "--quick" in args
    names, only, expect_only = [], [], False
    unknown = []
    for a in args:
        if expect_only:
            only.append(a)
            expect_only = False
        elif a == "--only":
            expect_only = True
        elif a.startswith("--only="):
            only.append(a.split("=", 1)[1])
        elif a == "--quick":
            pass
        elif a.startswith("--"):
            unknown.append(a)
        else:
            names.append(a)
    if expect_only:
        sys.exit("--only needs a bench name (e.g. --only planner_scale)")
    if unknown:
        sys.exit(f"unknown flags: {unknown} "
                 f"(supported: --quick, --only <bench>)")
    bad = [b for b in names + only if b not in BENCHES]
    if bad:
        sys.exit(f"unknown benches: {bad} (choose from {BENCHES})")
    names += only
    if quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    if not names:
        names = QUICK_BENCHES if quick else BENCHES
    failures = []
    for name in names:
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            mod.run()
            print(f"[bench_{name}: ok, {time.perf_counter() - t0:.1f}s]")
        except Exception as e:                          # noqa: BLE001
            failures.append(name)
            print(f"[bench_{name}: FAILED — {e!r}]")
    if failures:
        sys.exit(f"failed benches: {failures}")


if __name__ == "__main__":
    main()
