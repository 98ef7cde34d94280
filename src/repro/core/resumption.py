"""Resuming from a failed iteration (§6.2) — exact-semantics recovery.

A global batch of B micro-batches is partitioned over DP ranks (k = B/DP
each).  Gradients accumulate per rank until the end-of-iteration
all-reduce (Eq. 6).  On a rank failure:

* **Scenario #1** (before the all-reduce): the failed rank's accumulated
  gradients are lost; its k micro-batches are *redistributed round-robin*
  to the surviving ranks, which recompute them and fold them into their
  own accumulators (Eq. 7).  Survivors' partial results are reused — no
  global recompute.

* **Scenario #2** (all-reduce already started): the reduction proceeds in
  buckets (layer segments).  Buckets reduced *before* the failure already
  contain the failed rank's contribution and must not be overwritten;
  only the unreduced buckets take the redistributed recomputation.

Because micro-batches are deterministic functions of (step, index) — see
data.pipeline — recomputation is bit-identical, so the recovered gradient
equals the fault-free gradient.  tests/test_resumption.py asserts this.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax

from repro import obs
from repro.train.step import accumulate


# ---------------------------------------------------------------------------
# Micro-batch bookkeeping (the coordinator's iteration scheduler)
# ---------------------------------------------------------------------------


@dataclass
class MicroBatchIteration:
    """Tracks ownership and progress of the micro-batches of ONE global
    batch iteration across DP ranks."""

    n_ranks: int
    n_micro: int
    owners: Dict[int, List[int]] = field(default_factory=dict)
    done: Dict[int, List[int]] = field(default_factory=dict)
    failed_ranks: List[int] = field(default_factory=list)

    def __post_init__(self):
        if not self.owners:
            k, r = divmod(self.n_micro, self.n_ranks)
            idx = 0
            for rank in range(self.n_ranks):
                take = k + (1 if rank < r else 0)
                self.owners[rank] = list(range(idx, idx + take))
                idx += take
        for rank in range(self.n_ranks):
            self.done.setdefault(rank, [])

    def live_ranks(self) -> List[int]:
        return [r for r in range(self.n_ranks) if r not in self.failed_ranks]

    def complete(self, rank: int, mb: int) -> None:
        assert mb in self.owners[rank], (rank, mb)
        self.done[rank].append(mb)

    def pending(self, rank: int) -> List[int]:
        return [m for m in self.owners[rank] if m not in self.done[rank]]

    def fail_rank(self, rank: int) -> List[int]:
        """Mark ``rank`` failed and redistribute ALL of its micro-batches
        (its accumulator is lost) round-robin to survivors (Eq. 7).
        Returns the redistributed micro-batch ids."""
        assert rank not in self.failed_ranks
        self.failed_ranks.append(rank)
        orphans = list(self.owners[rank])
        self.owners[rank] = []
        self.done[rank] = []
        live = self.live_ranks()
        if not live:
            raise RuntimeError("all DP ranks failed; checkpoint restore "
                               "required")
        for i, mb in enumerate(orphans):
            self.owners[live[i % len(live)]].append(mb)
        return orphans

    def all_done(self) -> bool:
        return all(set(self.done[r]) == set(self.owners[r])
                   for r in self.live_ranks())


# ---------------------------------------------------------------------------
# Scenario #1: failure before the all-reduce
# ---------------------------------------------------------------------------


def run_iteration_with_failure(grad_fn: Callable, params,
                               microbatch_of: Callable[[int], dict],
                               n_ranks: int, n_micro: int,
                               fail_rank: Optional[int] = None,
                               fail_after_mb: int = 0):
    """Execute one gradient-accumulation iteration with an optional DP-rank
    failure after the failed rank completed ``fail_after_mb`` micro-batches.

    Single-host simulation of the distributed algebra: each rank's
    accumulator is a separate pytree; the final all-reduce is the sum over
    rank accumulators.  Returns (grad_sum, n_micro) ready for
    train.finalize_step.
    """
    it = MicroBatchIteration(n_ranks=n_ranks, n_micro=n_micro)
    acc: Dict[int, Optional[dict]] = {r: None for r in range(n_ranks)}

    def grads(rank: int, mb: int, redone: bool) -> None:
        # the span times the host's dispatch of an asynchronous grad_fn
        with obs.span("sev2.grads", rank=rank, mb=mb, redone=redone):
            g, _ = grad_fn(params, microbatch_of(mb))
            acc[rank] = accumulate(acc[rank], g)
        it.complete(rank, mb)

    # 1) ranks run until the failure point
    lost: List[int] = []
    if fail_rank is not None:
        for mb in it.owners[fail_rank][:fail_after_mb]:
            grads(fail_rank, mb, False)
        # 2) failure: pause, re-establish comms, redistribute (Eq. 7);
        # what the rank had done is lost with its accumulator
        lost = list(it.done[fail_rank])
        it.fail_rank(fail_rank)
        acc[fail_rank] = None

    # 3) all surviving ranks finish their (possibly grown) assignments
    for rank in it.live_ranks():
        for mb in it.pending(rank):
            grads(rank, mb, mb in lost)
    assert it.all_done()

    # 4) all-reduce over live ranks
    with obs.span("sev2.allreduce"):
        total = None
        for rank in it.live_ranks():
            if acc[rank] is not None:
                total = accumulate(total, acc[rank]) if total is not None \
                    else acc[rank]
    return total, n_micro


# ---------------------------------------------------------------------------
# Scenario #2: failure after the all-reduce started (bucketed reduction)
# ---------------------------------------------------------------------------


def bucket_masks(params, n_buckets: int) -> List[List[bool]]:
    """Split the flattened param leaves into ``n_buckets`` contiguous
    buckets (layer segments in Megatron terms)."""
    leaves = jax.tree.leaves(params)
    n = len(leaves)
    masks = []
    per = -(-n // n_buckets)
    for b in range(n_buckets):
        masks.append([per * b <= i < per * (b + 1) for i in range(n)])
    return masks


def merge_partial_reduce(treedef, reduced_full: List, survivor_sum: List,
                         recomputed: List, reduced_mask: Sequence[bool]):
    """Combine per-leaf:  already-reduced buckets keep the full sum
    (includes the failed rank); unreduced buckets take survivors' sums plus
    the redistributed recomputation.  All args are leaf lists."""
    out = []
    for i, is_reduced in enumerate(reduced_mask):
        if is_reduced:
            out.append(reduced_full[i])
        else:
            out.append(survivor_sum[i] + recomputed[i])
    return jax.tree.unflatten(treedef, out)


def run_scenario2(grad_fn: Callable, params,
                  microbatch_of: Callable[[int], dict],
                  n_ranks: int, n_micro: int, fail_rank: int,
                  n_buckets: int, buckets_reduced: int):
    """Failure after ``buckets_reduced`` of ``n_buckets`` gradient buckets
    were already all-reduced.  Returns (grad_sum, n_micro)."""
    it = MicroBatchIteration(n_ranks=n_ranks, n_micro=n_micro)
    acc: Dict[int, Optional[dict]] = {r: None for r in range(n_ranks)}
    # every rank finished its compute (all-reduce phase)
    for rank in range(n_ranks):
        for mb in it.owners[rank]:
            g, _ = grad_fn(params, microbatch_of(mb))
            acc[rank] = accumulate(acc[rank], g)
            it.complete(rank, mb)

    masks = bucket_masks(params, n_buckets)
    reduced_mask = [any(masks[b][i] for b in range(buckets_reduced))
                    for i in range(len(jax.tree.leaves(params)))]

    treedef = jax.tree.structure(params)
    full_sum = None
    for rank in range(n_ranks):
        full_sum = accumulate(full_sum, acc[rank]) if full_sum is not None \
            else acc[rank]
    full_leaves = jax.tree.leaves(full_sum)

    if buckets_reduced >= n_buckets:
        # failed worker's gradients fully reduced: proceed uninterrupted
        return full_sum, n_micro

    # survivors' sums for unreduced buckets
    survivor_sum = None
    for rank in range(n_ranks):
        if rank == fail_rank:
            continue
        survivor_sum = accumulate(survivor_sum, acc[rank]) \
            if survivor_sum is not None else acc[rank]
    # redistribute the failed rank's micro-batches; recompute them
    orphans = it.owners[fail_rank]
    recomputed = None
    for mb in orphans:
        g, _ = grad_fn(params, microbatch_of(mb))
        recomputed = accumulate(recomputed, g) if recomputed is not None \
            else accumulate(None, g)
    merged = merge_partial_reduce(
        treedef, full_leaves, jax.tree.leaves(survivor_sum),
        jax.tree.leaves(recomputed), reduced_mask)
    return merged, n_micro
