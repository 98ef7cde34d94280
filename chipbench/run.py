"""The on-chip benchmark of the Unicron reproduction.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One run is one process on the chips it finds.  It resolves the cell of
``BENCHMARK.json`` by name (its configuration file under
``chipbench/configs``, its traffic mix under ``chipbench/traffic``), exits
non-zero without a result unless JAX finds a TPU and as many chips as the
cell asks for, builds the cell from the seed, warms up every program the
window uses, measures for ``--seconds``, compares what the timed path
produced with the plain reference, and prints one JSON line last on
standard output.  With ``--trace 1`` the window is traced and the line
carries the cell's per-layer metrics instead of its end-to-end ones.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# libtpu's own log files stay inside the checkout too
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".chipbench", "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import flops, harness
    bench = harness.load_bench()
    cell, config, traffic = harness.resolve(bench, args.workload)
    devices = harness.require_chips(cell["chips"])
    import repro  # noqa: F401  -- the system under test must be present
    harness.use_compile_cache()
    ctx = harness.Context(
        bench=bench, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, devices=devices,
        limits=harness.load_json(os.path.join(
            harness.HERE, "limits", cell["name"] + ".json")),
        compiles=harness.CompileCounter(),
        tracer=harness.Tracer(bool(args.trace), cell["name"]),
        peak=flops.peaks(devices[0].device_kind))
    importlib.import_module("chipbench.kinds." + traffic["kind"]).run(ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())
