"""End-to-end training launcher: the Unicron-managed job on local devices.

Runs the full managed loop: deterministic data pipeline -> micro-batch
gradient accumulation -> AdamW, with the Unicron agent's online
statistical monitor watching iteration times, the hierarchical checkpoint
manager (in-memory + persistent tiers) saving state, and optional mid-run
failure injection exercising the §6.2 micro-batch redistribution path.

``build_job`` + ``run`` are the loop; the CLI below and ``chip_smoke.py``
at the repository root both drive it.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 50 --seq 128 --batch 8 --n-micro 4 --inject-fail 10
"""
from __future__ import annotations

import argparse
import os
import tempfile
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.core.agent import UnicronAgent
from repro.core.detection import ErrorKind
from repro.core.kvstore import KVStore
from repro.core.resumption import run_iteration_with_failure
from repro.data.pipeline import SyntheticLM, stack_microbatches
from repro.models.model import build_model
from repro.optim import AdamW, cosine_with_warmup
from repro.train.state import TrainState, init_train_state
from repro.train.step import finalize_step, make_grad_fn, make_train_step

#: persistent compilation cache used when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed directory of the checkout (git-ignored), so repeated runs hit it
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    ``COMPILE_CACHE_DIR``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclass
class ManagedJob:
    """One Unicron-managed training job: the jitted fused step (which
    donates its input state), the per-micro-batch gradient function the
    SEV2 redistribution path drives, the agent and the checkpoint
    manager."""

    cfg: ArchConfig
    opt: AdamW
    data: SyntheticLM
    mgr: CheckpointManager
    agent: UnicronAgent
    n_micro: int
    dp: int
    ckpt_every: int
    step_fn: Callable
    grad_fn: Callable

    def batch(self, step: int):
        return stack_microbatches(self.data.batch(step), self.n_micro)

    def compile(self, state: TrainState):
        """AOT-compile the fused step for this job's batch shape; later
        ``step`` calls run the compiled program.  Returns it."""
        self.step_fn = self.step_fn.lower(state, self.batch(0)).compile()
        return self.step_fn

    def step(self, state: TrainState, step: int) -> Tuple[TrainState, Dict]:
        """One fault-free fused iteration; ``state`` is donated.  Returns
        once the step is enqueued."""
        with obs.span("loop.batch", step=step):
            batch = self.batch(step)
        with obs.span("loop.dispatch", step=step):
            return self.step_fn(state, batch)

    def iteration_grads(self, params, step: int,
                        fail_rank: Optional[int] = None,
                        fail_after_mb: int = 0):
        """The resumable path's (grad_sum, count) of step ``step``; with
        ``fail_rank``, that DP rank dies after ``fail_after_mb``
        micro-batches and survivors absorb its micro-batches (Eq. 7)."""
        mb_size = self.data.global_batch // self.n_micro

        def microbatch_of(mb):
            return self.data.batch(step, start=mb * mb_size, n=mb_size)
        return run_iteration_with_failure(
            self.grad_fn, params, microbatch_of, n_ranks=self.dp,
            n_micro=self.n_micro, fail_rank=fail_rank,
            fail_after_mb=fail_after_mb)

    def recovered_step(self, state: TrainState, step: int,
                       fail_rank: int = 1, fail_after_mb: int = 0):
        """The SEV2 path: the agent reports the crash, the iteration
        completes through ``iteration_grads`` with exact semantics, and the
        optimizer applies it.  Returns (state, grad_norm)."""
        with obs.span("sev2.iteration", step=step):
            self.agent.report(ErrorKind.EXITED_ABNORMALLY, now=float(step))
            grad_sum, count = self.iteration_grads(
                state.params, step, fail_rank, fail_after_mb)
            with obs.span("sev2.optimizer", step=step):
                return finalize_step(self.opt, state, grad_sum, count)


def build_job(cfg: ArchConfig, *, seq: int, batch: int, n_micro: int,
              dp: int, lr: float, total_steps: int, ckpt_dir: str,
              ckpt_every: int, kernel: str = "jnp",
              seed: int = 0) -> Tuple[ManagedJob, TrainState]:
    """The job and its freshly initialised state (weights from ``seed``)."""
    model = build_model(cfg)
    opt = AdamW(lr=cosine_with_warmup(lr, 10, total_steps))
    state = init_train_state(model, opt, jax.random.PRNGKey(seed))
    job = ManagedJob(
        cfg=cfg, opt=opt,
        data=SyntheticLM(cfg, seq_len=seq, global_batch=batch, seed=seed),
        mgr=CheckpointManager(ckpt_dir, n_ranks=dp, persist_every=ckpt_every,
                              task=f"train-{cfg.name}"),
        agent=UnicronAgent(node_id=0, kv=KVStore()),
        n_micro=n_micro, dp=dp, ckpt_every=ckpt_every,
        step_fn=jax.jit(make_train_step(model, opt, n_micro, kernel=kernel),
                        donate_argnums=0),
        grad_fn=make_grad_fn(model, kernel=kernel))
    return job, state


def run(job: ManagedJob, state: TrainState, steps: int, *, start: int = 0,
        inject_fail: Optional[int] = None,
        log: Callable[[str], Any] = print
        ) -> Tuple[TrainState, List[Dict]]:
    """The managed loop over steps ``start .. start+steps-1``.  Step
    ``inject_fail`` runs the SEV2 path (``recovered_step``); every other
    step is the fused step, its time fed to the agent's monitor.  After
    every ``job.ckpt_every``-th completed step the state is saved (both
    tiers).  Each step's time runs from the start of its ``loop.step`` span
    to the end of its ``loop.sync`` span (``block_until_ready`` and the
    host reads of its numbers).  Returns the state and one record per
    step."""
    records = []
    for step in range(start, start + steps):
        kind = "recovered" if step == inject_fail else "fused"
        with obs.span("loop.step", step=step, kind=kind) as whole:
            if kind == "recovered":
                log(f"step {step}: INJECTING rank-1 failure mid-iteration")
                state, gnorm = job.recovered_step(state, step)
                with obs.span("loop.sync", step=step) as sync:
                    rec = {"step": step, "kind": kind, "loss": None,
                           "grad_norm": float(jax.block_until_ready(gnorm))}
            else:
                state, metrics = job.step(state, step)
                with obs.span("loop.sync", step=step) as sync:
                    state, metrics = jax.block_until_ready((state, metrics))
                    rec = {"step": step, "kind": kind,
                           "loss": float(metrics["loss"]),
                           "grad_norm": float(metrics["grad_norm"])}
            rec["seconds"] = sync.t1 - whole.t0
            if kind == "fused":
                with obs.span("loop.monitor", step=step):
                    job.agent.observe_iteration(rec["seconds"])
            rec["saved"] = (step + 1) % job.ckpt_every == 0
            if rec["saved"]:
                job.mgr.save(rank=0, step=step + 1, state=state)
            log(f"step {step:4d} {rec['kind']} loss={rec['loss']} "
                f"grad_norm={rec['grad_norm']:.4f} ({rec['seconds']:.3f}s)"
                + (" saved" if rec["saved"] else ""))
        records.append(rec)
    return state, records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the 2-layer smoke variant (CPU friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n-micro", type=int, default=4)
    ap.add_argument("--dp", type=int, default=4,
                    help="simulated DP ranks for the resumable path")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "unicron_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--inject-fail", type=int, default=None,
                    help="inject a DP-rank failure at this step")
    ap.add_argument("--kernel", default="jnp",
                    choices=["jnp", "pallas", "flash"])
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"params={cfg.param_count() / 1e6:.1f}M")
    job, state = build_job(
        cfg, seq=args.seq, batch=args.batch, n_micro=args.n_micro,
        dp=args.dp, lr=args.lr, total_steps=args.steps,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        kernel=args.kernel)
    state, _ = run(job, state, args.steps, inject_fail=args.inject_fail)
    print("done;", f"final step={int(state.step)}")


if __name__ == "__main__":
    main()
