"""Training cells: the Unicron-managed job of ``launch/train.py``
(``build_job``, ``run``) on the model of the configuration's
``model_type`` (``chipbench/models/<model_type>.py``), driven from the
seed.

Set-up builds the job, gives it the benchmark's weights (one jitted call
from the seed) and the benchmark's token feed, and drives it through the
traffic's ``setup`` actions with the window's own calls: the first three
optimizer steps, which the reference follows.  The window then runs the
managed loop one ``run`` call a step for ``--seconds``, injecting the
traffic's failures:

- ``sev2``: DP rank ``sev2_fail_rank`` dies after a number of its
  micro-batches (drawn from the seed, each value of ``sev2_fail_after_mb``
  equally often); ``run`` completes the iteration through the
  redistribution path (``ManagedJob.recovered_step``).  Charged the
  recovered iteration.
- ``sev1``: the node's device state and local snapshot are lost; the
  state comes back from the ring neighbour's in-memory copy and the job
  redoes the steps since.  Charged from injection until the failed step
  is committed again.
"""
from __future__ import annotations

import math
import os
import time
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, inputs
from chipbench.reference import dense_lm

NEVER = 1 << 30


def quiet(_msg):
    return None


def model_of(cfg: dict):
    """The module of the configuration's model type."""
    return harness.load_module("models", cfg["model_type"])


def fail_after_stream(traffic: dict, seed: int) -> Iterator[int]:
    """Micro-batch counts after which the SEV2 rank dies: every value
    once in each round, in an order drawn from the seed."""
    values = traffic.get("sev2_fail_after_mb", [0])
    rng = np.random.default_rng([seed, 7])
    while True:
        yield from (int(v) for v in rng.permutation(values))


def build(cfg: dict, traffic: dict, seed: int):
    """The managed job and its state, with the benchmark's weights and
    token feed."""
    from repro.launch.train import build_job
    from repro.train.state import TrainState
    job_cfg, hp = cfg["job"], cfg["job"]["adamw"]
    model = model_of(cfg)
    job, prog_state = build_job(
        model.arch_config(cfg), seq=job_cfg["seq_len"],
        batch=job_cfg["sequences_per_step"],
        n_micro=job_cfg["micro_batches"], dp=job_cfg["dp_ranks"],
        lr=job_cfg["lr"], total_steps=job_cfg["total_steps"],
        ckpt_dir=os.path.join(harness.STATE_DIR, "ckpt"),
        ckpt_every=NEVER, kernel=job_cfg["kernel"], seed=0)
    opt = job.opt
    if (opt.b1, opt.b2, opt.eps, opt.weight_decay, opt.grad_clip) != (
            hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"],
            hp["grad_clip"]):
        raise ValueError(f"build_job's optimizer {opt} is not the one the "
                         f"configuration states")
    like = jax.tree.map(lambda x: (x.shape, x.dtype), prog_state.params)
    jax.tree.map(lambda x: x.delete(), prog_state)
    del prog_state
    job.mgr.persist_every = NEVER        # no persistent write in a run
    job.data = inputs.TokenFeed(seed, cfg["vocab_size"], job_cfg["seq_len"],
                                job_cfg["sequences_per_step"])
    key = inputs.key_of(seed, inputs.WEIGHTS)

    @jax.jit
    def make_state(k):
        p = model.params(cfg, k)
        return TrainState(p, opt.init(p), jnp.zeros((), jnp.int32))
    state = make_state(key)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), state.params)
    if got != like:
        raise ValueError("the benchmark's weights do not have the layout "
                         "of the program's model")
    return job, state


def instrument(job, spans: harness.Spans, traffic: dict, seed: int) -> None:
    """Wrap the job's calls into the step, the SEV2 path and the snapshot
    in the benchmark's spans; each ends in ``block_until_ready``."""
    step_fn, recovered, save = job.step, job.recovered_step, job.mgr.save
    rank = traffic.get("sev2_fail_rank", 1)
    after = fail_after_stream(traffic, seed)

    def step(state, s):
        with spans("train.step"):
            return jax.block_until_ready(step_fn(state, s))

    def recovered_step(state, s):
        with spans("sev2.iteration"):
            return jax.block_until_ready(recovered(
                state, s, fail_rank=rank, fail_after_mb=next(after)))

    def snapshot(rank, step, state):
        with spans("ckpt.snapshot"):
            return save(rank=rank, step=step, state=state)

    job.step, job.recovered_step, job.mgr.save = step, recovered_step, snapshot


def sev1_restore(job, state, spans: harness.Spans):
    """The node is lost: its device state and its own snapshot go; the
    state comes back from the ring neighbour's in-memory copy."""
    with spans("ckpt.restore"):
        jax.tree.map(lambda x: x.delete(), state)
        job.mgr.drop_rank(0)
        snap, at, src = job.mgr.restore(0, like=None)
        if src != "inmemory_replica":
            raise RuntimeError(f"SEV1 restore came from {src!r}")
        state = jax.block_until_ready(jax.device_put(snap))
    return state, at


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _change_norms(master, params0):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(m - p.astype(jnp.float32))))
        for m, p in zip(jax.tree.leaves(master), jax.tree.leaves(params0))])


def warm(job, state, cfg: dict, traffic: dict, seed: int,
         spans: harness.Spans):
    """The traffic's set-up actions through the window's own calls.
    Returns the state after three optimizer steps, the step the window
    starts at, and the program's readings of those three steps."""
    from repro.launch.train import run
    hp = cfg["job"]["adamw"]
    rd: Dict[str, list] = {"loss": [], "gnorm": []}
    step = 0
    job.ckpt_every = NEVER           # set-up snapshots only where it says
    for action in traffic["setup"]:
        if action in ("step", "sev2"):
            state, recs = run(job, state, 1, start=step, log=quiet,
                              inject_fail=step if action == "sev2" else None)
            rd["loss"].append(recs[0]["loss"])
            rd["gnorm"].append(recs[0]["grad_norm"])
            if step == 0:
                g0 = recs[0]["grad_norm"]
                unclip = max(1.0, g0 / hp["grad_clip"]) / (1.0 - hp["b1"])
                rd["grad_leaf"] = np.asarray(_norms(state.opt.mu),
                                             np.float64) * unclip
            step += 1
        elif action == "snapshot":
            job.mgr.save(rank=0, step=step, state=state)
        elif action == "sev1":
            state, step = sev1_restore(job, state, spans)
        else:
            raise ValueError(f"unknown set-up action {action!r}")
    if step != 3:
        raise ValueError(f"set-up leaves the state at step {step}, not 3")
    job.ckpt_every = traffic["snapshot_every"] or NEVER
    rd["loss"], rd["gnorm"] = rd["loss"][:3], rd["gnorm"][:3]
    params0 = weights(cfg, seed)
    master = state.opt.master if state.opt.master is not None \
        else state.params
    rd["change_leaf"] = np.asarray(_change_norms(master, params0),
                                   np.float64)
    del params0
    return state, step, rd


def weights(cfg: dict, seed: int):
    """The benchmark's weights of ``seed``, made again on the device."""
    return jax.jit(lambda k: model_of(cfg).params(cfg, k))(
        inputs.key_of(seed, inputs.WEIGHTS))


def period(traffic: dict) -> int:
    """Steps in one period of the traffic's schedule of snapshots and
    failure kinds; the window ends only at the end of a whole period."""
    every = traffic["fail_every"] * len(traffic["fail_kinds"])
    return math.lcm(traffic["snapshot_every"] or 1, every or 1)


def window(job, state, step: int, traffic: dict, seconds: float,
           spans: harness.Spans):
    """The managed loop for whole periods of the schedule, until
    ``seconds`` have passed; returns the state, the window's host
    interval, the committed steps and each failure's recovery."""
    from repro.launch.train import run
    every, kinds = traffic["fail_every"], traffic["fail_kinds"]
    per = period(traffic)
    start, w = step, 0
    failures: List[Tuple[str, float]] = []
    with spans("window"):
        t0 = time.perf_counter()
        while True:
            kind = None
            if every and (w + 1) % every == 0:
                kind = kinds[(w // every) % len(kinds)]
            t = time.perf_counter()
            if kind == "sev1":
                state, at = sev1_restore(job, state, spans)
                state, _ = run(job, state, step - at + 1, start=at,
                               log=quiet)
            else:
                state, _ = run(job, state, 1, start=step, log=quiet,
                               inject_fail=step if kind == "sev2" else None)
            if kind:
                failures.append((kind, time.perf_counter() - t))
            step, w = step + 1, w + 1
            if w % per == 0 and time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
    return state, (t0, t1), step - start, failures


def recover_s(failures: List[Tuple[str, float]]) -> float:
    """Mean over the failure kinds of each kind's mean recovery, so that
    every run averages the same mix of kinds."""
    by_kind: Dict[str, List[float]] = {}
    for kind, secs in failures:
        by_kind.setdefault(kind, []).append(secs)
    if not by_kind:
        return float("nan")
    return sum(sum(v) / len(v) for v in by_kind.values()) / len(by_kind)


def reference(cfg: dict, seed: int, dtype: str = "float32", rows=None):
    """The plain reference's readings of the first three steps."""
    feed = inputs.TokenFeed(seed, cfg["vocab_size"], cfg["job"]["seq_len"],
                            cfg["job"]["sequences_per_step"])
    params0 = weights(cfg, seed)
    return dense_lm.train_readings(params0, [feed.tokens(s) for s in
                                             range(3)],
                                   cfg, cfg["job"], dtype=dtype, rows=rows,
                                   loss=model_of(cfg).seq_loss)


def free(job, state) -> None:
    """Release the program's device state and host snapshots."""
    jax.tree.map(lambda x: x.delete() if not x.is_deleted() else None,
                 state)
    job.mgr.store._local.clear()
    job.mgr.store._replica.clear()


def run(ctx) -> None:
    cfg, traffic, cell = ctx.config, ctx.traffic, ctx.cell
    job_cfg = cfg["job"]
    spans = harness.Spans(annotate=ctx.trace)
    job, state = build(cfg, traffic, ctx.seed)
    instrument(job, spans, traffic, ctx.seed)
    state, step, prog = warm(job, state, cfg, traffic, ctx.seed, spans)
    compiles0 = ctx.compiles.count
    ctx.tracer.start()
    t_window = time.perf_counter()
    state, win, steps, failures = window(job, state, step, traffic,
                                         ctx.seconds, spans)
    ctx.tracer.stop()
    compiles = ctx.compiles.count - compiles0
    device = harness.device_info(ctx.devices, ctx.tracer.summary)
    free(job, state)
    del job, state

    tokens_per_step = job_cfg["seq_len"] * job_cfg["sequences_per_step"]
    window_s = win[1] - win[0]
    rate = steps * tokens_per_step / window_s
    # one quantity under two names: a cell with failures reports it as
    # goodput, under a bound of its own
    e2e = {"setup_s": t_window - ctx.t_start, "tokens_per_s": rate,
           "goodput_tokens_per_s": rate}
    if traffic["fail_every"]:
        e2e["recover_s"] = recover_s(failures)
    run_rec = harness.Run(
        cell=cell, config=cfg, traffic=traffic, spans=spans, window=win,
        counters={"steps": steps, "failures": failures,
                  "tokens_per_step": tokens_per_step,
                  "train_flops_per_step": model_of(cfg).train_flops(
                      cfg, job_cfg["seq_len"],
                      job_cfg["sequences_per_step"]),
                  "chips": len(ctx.devices), "compiles_in_window": compiles},
        summary=ctx.tracer.summary, peak=ctx.peak)
    ms = np.array(spans.of("train.step", *win) or [math.nan]) * 1e3
    print(f"window: {steps} steps in {window_s:.3f} s, failures "
          f"{[(k, round(s, 3)) for k, s in failures]}, compiles in window "
          f"{compiles}; fused step ms p50 {np.percentile(ms, 50):.1f} "
          f"p99 {np.percentile(ms, 99):.1f} max {ms.max():.1f}, "
          f"{int((ms > 1.1 * np.median(ms)).sum())} over 1.1x the median",
          flush=True)

    ref = reference(cfg, ctx.seed)
    values = dense_lm.compare(prog, ref)
    checks = [harness.Check(k, v, ctx.limits[k]) for k, v in values.items()]
    result = {"attempted": steps, "failed": 0,
              "device": device}
    if ctx.trace:
        result["metrics"] = harness.per_layer(ctx.bench, run_rec)
        result["breakdown"] = ctx.tracer.summary.breakdown()
    else:
        result["metrics"] = harness.end_to_end(ctx.bench, cell, e2e)
    harness.emit(result, checks)
