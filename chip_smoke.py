"""Smoke run of the Unicron reproduction on a TPU: the main path, end to end.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded step on a 2x2 host

One process, phases in order; any failed check raises and exits non-zero.

(a) Device check: the first device must be a TPU and the Pallas kernels
    must compile (``REPRO_PALLAS_INTERPRET`` may not force interpret mode).
(b) Managed training (``launch.train.build_job``/``run``) of qwen3-4b at
    its published widths, cut to one chip's share
    (``configs.qwen3_4b.ONE_CHIP_CUT``), at 4096 tokens a sequence with the
    Pallas flash-attention forward compiled into the step: a few fused
    steps with finite loss, one save that reaches both checkpoint tiers,
    restores from the in-memory and the persistent tier that equal the
    saved state bit for bit, and one injected SEV2 iteration whose gradient
    equals the fault-free iteration's up to f32 summation order and whose
    step agrees with the fused fault-free step; the in-memory snapshot,
    which keeps the transfer's own host arrays, is unchanged after three
    donated steps and the next save.
(c) The planner's device program: a fused ``PlanTable`` whole-table
    rebuild at the paper's headline fleet (n=1024, m=32) in one dispatch,
    with no retrace on a same-signature rebuild, within 1e-6 of
    ``solve_reference``, and as close to the batched host engine as the
    chip's emulated float64 allows (``F64_EMULATED_RTOL``); then the same
    rebuild on the compiled Pallas max-plus kernel within the documented
    1e-6 float32 budget.

``--chips 4`` runs only the sharded train step on a ("data", "model")
2x2 mesh at the same cut and compares it with the same step on one
device of that host.

Each phase prints JSON lines; the last line of stdout is the verdict,
``{"ok": true, "device": {...}}``.  Weights and data come from a seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import inmemory  # noqa: E402
from repro.configs.qwen3_4b import ONE_CHIP_CUT, one_chip_share  # noqa: E402
from repro.kernels.pallas_config import resolve_interpret  # noqa: E402
from repro.launch.train import build_job, run, use_compile_cache  # noqa: E402

SEQ = 4096
#: relative bound on the loss and gradient norm of two programs that
#: compute the same bf16 step with different fusion or partitioning: each
#: bf16 element may round one ulp (2^-8 relative) apart, and a mean or a
#: norm over many elements moves by no more than that.
BF16_RTOL = 2.0 ** -8
#: relative L2 bound between the recovered and the fault-free gradient
#: sums: the same per-micro-batch gradients, added in another order in f32
#: (2^-24 per addition, three additions).
GRAD_RTOL = 1e-6
PLAN_RTOL = 1e-6          # the documented float32 budget of the planner
#: bound on the fused float64 program's totals against the host's IEEE
#: float64 engine.  The v5e has no float64 unit: XLA emulates it with pairs
#: of float32 (about 48 significant bits), so each of the few dozen
#: additions behind a total may round at ~2^-48 relative, where the host
#: rounds at 2^-53.
F64_EMULATED_RTOL = 1e-12
REF_KEYS = ("fault:0", "fault:31", "finish:0", "finish:31", "join:1")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def bit_equal(a, b) -> bool:
    if jax.tree.structure(a) != jax.tree.structure(b):
        return False
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape or not np.array_equal(
                x.reshape(-1).view(np.uint8), y.reshape(-1).view(np.uint8)):
            return False
    return True


@jax.jit
def _sq_err(a, b):
    """(||a - b||^2, ||b||^2) for one leaf."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))


def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over all leaves of two trees."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        n, d = _sq_err(x, y)
        num, den = num + float(n), den + float(d)
    return (num / den) ** 0.5


def rel(a, b) -> float:
    return abs(float(a) - float(b)) / abs(float(b))


# ---------------------------------------------------------------------------
# (a) device check
# ---------------------------------------------------------------------------


def device_check(chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu",
          f"first device is {dev.platform!r}, not a TPU; there is no CPU "
          f"fallback")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    check(not resolve_interpret(),
          "REPRO_PALLAS_INTERPRET forces the Pallas kernels into interpret "
          "mode")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    emit("device", **info, compile_cache=use_compile_cache())
    return info


# ---------------------------------------------------------------------------
# (b) managed training at full width
# ---------------------------------------------------------------------------


def train_phase() -> None:
    cfg = one_chip_share()
    emit("train.config", arch=cfg.name, source=cfg.source, cut=ONE_CHIP_CUT,
         d_model=cfg.d_model, d_ff=cfg.d_ff, heads=cfg.attn.n_heads,
         kv_heads=cfg.attn.n_kv_heads, head_dim=cfg.attn.head_dim,
         params=cfg.param_count(), seq=SEQ, batch=4, n_micro=4, dp=2,
         kernel="pallas")
    with tempfile.TemporaryDirectory() as ckpt_dir:
        job, state = build_job(cfg, seq=SEQ, batch=4, n_micro=4, dp=2,
                               lr=3e-4, total_steps=100, ckpt_dir=ckpt_dir,
                               ckpt_every=3, kernel="pallas")
        t0 = time.perf_counter()
        compiled = job.compile(state)
        compile_s = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        check("tpu_custom_call" in compiled.as_text(),
              "no compiled Pallas kernel in the train step")
        emit("train.compile", seconds=compile_s,
             argument_bytes=ma.argument_size_in_bytes,
             temp_bytes=ma.temp_size_in_bytes,
             alias_bytes=ma.alias_size_in_bytes)

        # 1. fused steps; the third completes a save to both tiers
        state, recs = run(job, state, 3, log=lambda s: None)
        for r in recs:
            emit("train.step", **r)
        check(all(np.isfinite(r["loss"]) for r in recs), "non-finite loss")
        check(int(state.step) == 3, "step counter")
        emit("train.steady", warm_step_s=min(r["seconds"] for r in recs[1:]),
             tokens_per_step=4 * SEQ, peak_bytes_in_use=peak_bytes())

        # 2-3. the save reached both tiers; each restores bit for bit
        check(recs[-1]["saved"], "no checkpoint save")
        mem, at, src = job.mgr.restore(0, like=state)
        check(src == "inmemory_local" and at == 3, f"in-memory tier: {src}")
        check(bit_equal(mem, state), "in-memory restore differs")
        check(not any(inmemory.copies(x) for x in jax.tree.leaves(state)),
              "the snapshot copies leaves on the chip")
        frozen = jax.tree.map(np.array, mem)
        job.mgr.drop_rank(0)                    # the host and its neighbour
        job.mgr.drop_rank(job.mgr.store.neighbor(0))
        t0 = time.perf_counter()
        disk, at, src = job.mgr.restore(0, like=state)
        restore_s = time.perf_counter() - t0
        check(src == "persistent" and at == 3, f"persistent tier: {src}")
        check(bit_equal(disk, state), "persistent restore differs")
        del disk
        emit("train.restore", inmemory="bit-equal", persistent="bit-equal",
             persistent_restore_s=restore_s)

        # 4. SEV2: rank 1 dies before its first micro-batch completes;
        # rank 0 absorbs both of its micro-batches (Eq. 7)
        want, _ = job.iteration_grads(state.params, 3)
        want = jax.device_get(want)
        got, _ = job.iteration_grads(state.params, 3, fail_rank=1)
        grad_err = rel_l2(got, want)
        del got, want
        rec_state, recs = run(job, state, 1, start=3, inject_fail=3,
                              log=lambda s: None)
        check(int(rec_state.step) == 4, "recovered step counter")
        del state, rec_state
        state, ff = job.step(jax.device_put(mem), 3)      # fault-free
        gn_err = rel(recs[0]["grad_norm"], ff["grad_norm"])
        # 5. the snapshot keeps the transfer's host arrays: the donated
        # fault-free step, two more and the next in-memory save leave its
        # bytes as they were
        job.mgr.persist_every = 100        # the persistent tier is checked
        state, recs2 = run(job, state, 2, start=4, log=lambda s: None)
        check(recs2[-1]["saved"], "no second checkpoint save")
        check(bit_equal(mem, frozen), "a snapshot changed after it was "
              "taken")
        del state, frozen
        emit("train.snapshot", unchanged="bit-equal", donated_steps_after=3,
             saves_after=1)
        emit("train.sev2", recovered_s=recs[0]["seconds"],
             grad_sum_rel_l2=grad_err, grad_rtol=GRAD_RTOL,
             grad_norm_vs_fused_rel=gn_err, bf16_rtol=BF16_RTOL,
             peak_bytes_in_use=peak_bytes())
        check(grad_err <= GRAD_RTOL, f"recovered gradient off the "
              f"fault-free one by {grad_err:.3g}")
        check(gn_err <= BF16_RTOL, f"recovered step's gradient norm off the "
              f"fused step's by {gn_err:.3g}")


# ---------------------------------------------------------------------------
# (c) the planner's device program
# ---------------------------------------------------------------------------


def planner_phase() -> None:
    from benchmarks.bench_planner_scale import _reference_reward, _rel_err
    from benchmarks.common import fleet_tasks
    from repro.core import planner
    from repro.core.costmodel import A800

    n, m = 1024, 32
    tasks = fleet_tasks(m)

    def rebuild(assignment, engine):
        table = planner.PlannerCache().table(tasks, assignment, A800, 3600.0,
                                             120.0, engine=engine)
        t0 = time.perf_counter()
        totals = table.rebuild_values()
        return table, totals, time.perf_counter() - t0

    # the first fused rebuild compiles; the churned one (same n, so the
    # same schedule signature) must reuse that program
    states = {"cold": [n // m] * m,
              "warm": [n // m + 1, n // m - 1] + [n // m] * (m - 2)}
    exact = {k: rebuild(a, "batched")[1] for k, a in states.items()}   # f64
    ref = {k: _reference_reward(tasks, k, states["cold"], m)
           for k in REF_KEYS}
    for backend in ("numpy", "pallas"):
        planner.set_maxplus_backend(backend)
        try:
            check(backend == "numpy" or not resolve_interpret(),
                  "the Pallas max-plus kernel would be interpreted")
            secs, worst, n_equal = {}, 0.0, 0
            for name, assignment in states.items():
                table, got, secs[name] = rebuild(assignment, "fused")
                want = exact[name]
                check(table.batch_stats["device_dispatches"] == 1,
                      f"{backend}: {table.batch_stats}")
                check(set(got) == set(want), "scenario sets differ")
                err = max(_rel_err(got[k], want[k]) for k in want)
                worst = max(worst, err)
                n_equal += sum(got[k] == want[k] for k in want)
                tol = F64_EMULATED_RTOL if backend == "numpy" else PLAN_RTOL
                check(err < tol, f"{backend}: fused totals off the host "
                      f"float64 engine by {err:.3g}")
                if name == "cold":
                    ref_err = max(_rel_err(got[k], ref[k]) for k in REF_KEYS)
                    check(ref_err < PLAN_RTOL,
                          f"off solve_reference by {ref_err:.3g}")
            prog = planner._FUSED_PROGRAMS[table._fused_signature()]
            check(prog.traces() == 1, f"retraced: {prog.traces()} traces")
            with jax.enable_x64(True):
                rows = jax.ShapeDtypeStruct((m, prog.sched.n1), jnp.float64)
                text = prog._fn.lower(rows, rows, jax.ShapeDtypeStruct(
                    (2 * m + 1,), jnp.int32)).as_text()
            check((backend == "pallas") == ("tpu_custom_call" in text),
                  f"{backend}: Pallas kernel presence in the program")
        finally:
            planner.set_maxplus_backend(None)
        emit("planner", backend=backend, n=n, m=m, scenarios=len(got),
             dispatches_per_rebuild=1, traces=prog.traces(),
             cold_rebuild_s=secs["cold"], warm_rebuild_s=secs["warm"],
             worst_rel_vs_f64_batched=worst, rtol=tol,
             bit_equal_totals=f"{n_equal}/{2 * len(got)}", ref_rel=ref_err,
             ref_keys=list(REF_KEYS), peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------------
# --chips 4: the sharded train step against one device of the host
# ---------------------------------------------------------------------------


def sharded_phase() -> None:
    from jax.sharding import AxisType

    from repro.data.pipeline import SyntheticLM, stack_microbatches
    from repro.models.model import build_model
    from repro.optim import AdamW, cosine_with_warmup
    from repro.sharding import batch_specs, to_named, train_state_specs
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    cfg = one_chip_share()
    batch_size, n_micro = 4, 2          # 2 sequences a micro-batch: 1 per DP
    # GSPMD cannot partition a Mosaic kernel, so both sides run attention
    # through the jnp flash-style VJP ("flash"), which it can.
    kernel = "flash"
    model = build_model(cfg)
    opt = AdamW(lr=cosine_with_warmup(3e-4, 10, 100))
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    emit("sharded.config", arch=cfg.name, cut=ONE_CHIP_CUT, seq=SEQ,
         batch=batch_size, n_micro=n_micro, mesh=dict(mesh.shape),
         kernel=kernel)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    batch = stack_microbatches(
        SyntheticLM(cfg, seq_len=SEQ, global_batch=batch_size).batch(0),
        n_micro)
    s_shard = to_named(mesh, train_state_specs(jax.eval_shape(
        lambda s: s, state), mesh))
    b_shard = to_named(mesh, batch_specs(jax.eval_shape(lambda b: b, batch),
                                         ("data",), 2, stacked=True))
    step = make_train_step(model, opt, n_micro, kernel=kernel)
    t0 = time.perf_counter()
    one = jax.jit(step, donate_argnums=0).lower(state, batch).compile()
    four = jax.jit(step, donate_argnums=0, out_shardings=(s_shard, None)) \
        .lower(*jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            (state, batch), (s_shard, b_shard))).compile()
    emit("sharded.compile", seconds=time.perf_counter() - t0,
         mesh_temp_bytes=four.memory_analysis().temp_size_in_bytes,
         one_temp_bytes=one.memory_analysis().temp_size_in_bytes)

    # the one-device step first, alone on device 0; then the mesh
    host0 = jax.device_get(state)
    _, m1 = jax.block_until_ready(one(state, batch))
    del state
    sh_state = jax.device_put(host0, s_shard)
    sh_batch = jax.device_put(batch, b_shard)
    t0 = time.perf_counter()
    new4, m4 = jax.block_until_ready(four(sh_state, sh_batch))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(four(new4, sh_batch))
    warm_s = time.perf_counter() - t0
    loss_err = rel(m4["loss"], m1["loss"])
    gn_err = rel(m4["grad_norm"], m1["grad_norm"])
    emit("sharded.step", loss_one=float(m1["loss"]),
         loss_mesh=float(m4["loss"]), loss_rel=loss_err,
         grad_norm_one=float(m1["grad_norm"]),
         grad_norm_mesh=float(m4["grad_norm"]), grad_norm_rel=gn_err,
         bf16_rtol=BF16_RTOL, first_step_s=first_s, warm_step_s=warm_s,
         peak_bytes_in_use=peak_bytes())
    check(loss_err <= BF16_RTOL and gn_err <= BF16_RTOL,
          "the sharded step disagrees with the one-device step")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    info = device_check(args.chips)
    if args.chips == 4:
        sharded_phase()
    else:
        train_phase()
        planner_phase()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
