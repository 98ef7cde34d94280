"""Readings that set the limits of ``correct`` (run on the chip, never by
the benchmark's own runs).

    python3 chipbench/calibrate.py train --seeds 12 --variants 3 \
        --cells qwen3-4b.faults qwen3-4b.steady
    python3 chipbench/calibrate.py fleet --seeds 12 --variants 3

``train``: for each seed, the set-up of the given training cells of one
configuration (the first three optimizer steps through the window's own
calls), and the float32 reference once; each cell's numbers against it
are the lower readings.
On the first ``--variants`` seeds it also reads the control (the
reference with every product's operands rounded to float8 e4m3, one step
below the configuration's bfloat16) and the planted faults, put in the
program's place: half of each step's batch left out, and the SEV2 step's
exchange left out (its second rank's micro-batches missing).

``fleet``: for each seed, a short window of the replan cell's loop and
the reference's comparison; on the first ``--variants`` seeds the control
(the reference's dynamic program in float32 in the program's place) and
an altered answer (eight workers moved between two tasks of a dispatched
plan).

One JSON line per reading goes to standard output and to ``--out``
(default ``.chipbench/calibrate_<kind>.jsonl`` in the checkout).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# libtpu's own log files stay inside the checkout too
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".chipbench", "tpu_logs"))
os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

SEED0 = 3_000_000_017


def out_file(path: str):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return open(path, "w")


def emit(f, **rec):
    line = json.dumps(rec, default=float)
    print(line, flush=True)
    f.write(line + "\n")
    f.flush()


def train(args, f) -> None:
    import jax
    from chipbench import harness
    from chipbench.kinds import train as tk
    from chipbench.reference import dense_lm
    bench = harness.load_bench()
    cells = [harness.resolve(bench, w) for w in args.cells]
    cfg = cells[0][1]
    if any(c[1] != cfg for c in cells):
        raise SystemExit("the cells calibrated together share a configuration")
    n = cfg["job"]["sequences_per_step"]
    variants = {
        "control_fp8": dict(dtype="float8_e4m3fn"),
        "bf16_operands": dict(dtype="bfloat16"),
        "half_batch": dict(rows=[list(range(n // 2))] * 3),
        "exchange_left_out": dict(rows=[None, list(range(n // 2)), None]),
    }
    for i in range(args.seeds):
        seed = SEED0 + 7919 * i
        progs = {}
        for cell, config, traffic in cells:
            spans = harness.Spans()
            t0 = time.perf_counter()
            job, state = tk.build(config, traffic, seed)
            tk.instrument(job, spans, traffic, seed)
            state, _, progs[cell["name"]] = tk.warm(job, state, config,
                                                    traffic, seed, spans)
            tk.free(job, state)
            del job, state
            emit(f, seed=seed, what="program", cell=cell["name"],
                 seconds=time.perf_counter() - t0,
                 loss=progs[cell["name"]]["loss"],
                 gnorm=progs[cell["name"]]["gnorm"])
        t0 = time.perf_counter()
        ref = tk.reference(cfg, seed)
        emit(f, seed=seed, what="reference", seconds=time.perf_counter() - t0,
             loss=ref["loss"], gnorm=ref["gnorm"],
             leaves=len(ref["grad_leaf"]),
             grad_leaf_min_over_median=float(
                 min(ref["grad_leaf"]) / sorted(ref["grad_leaf"])[
                     len(ref["grad_leaf"]) // 2]))
        for name, prog in progs.items():
            emit(f, seed=seed, what="lower", cell=name,
                 **dense_lm.compare(prog, ref))
        if i == 0:
            names = dense_lm.leaf_names(jax.eval_shape(
                lambda k: tk.model_of(cfg).params(cfg, k),
                jax.random.PRNGKey(0)))
            prog = progs[cells[-1][0]["name"]]
            emit(f, seed=seed, what="leaves", names=names,
                 **{f"{side}_{k}": list(map(float, d[k]))
                    for side, d in (("program", prog), ("reference", ref))
                    for k in ("grad_leaf", "change_leaf")})
        if i < args.variants:
            for vname, kw in variants.items():
                t0 = time.perf_counter()
                try:
                    got = tk.reference(cfg, seed, **kw)
                    vals = dense_lm.compare(got, ref)
                except Exception as e:      # a control that crashes
                    vals = {"error": repr(e)}
                emit(f, seed=seed, what=vname,
                     seconds=time.perf_counter() - t0, **vals)


def fleet(args, f) -> None:
    from chipbench import harness
    from chipbench.kinds import fleet as fk
    bench = harness.load_bench()
    cell, config, traffic = harness.resolve(bench, "fleet-1024x32.replan")
    for i in range(args.seeds):
        seed = SEED0 + 7919 * i
        t0 = time.perf_counter()
        vals = fk.calibration_readings(config, traffic, seed, args.seconds,
                                       variants=i < args.variants)
        for what, v in vals.items():
            emit(f, seed=seed, what=what, seconds=time.perf_counter() - t0,
                 **v)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("kind", choices=("train", "fleet"))
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--variants", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--cells", nargs="+",
                    default=["qwen3-4b.faults", "qwen3-4b.steady"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from chipbench import harness
    harness.require_chips(1)
    harness.use_compile_cache()
    out = args.out or os.path.join(ROOT, ".chipbench",
                                   f"calibrate_{args.kind}.jsonl")
    with out_file(out) as f:
        {"train": train, "fleet": fleet}[args.kind](args, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
