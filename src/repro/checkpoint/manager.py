"""Hierarchical checkpoint manager — the *nearest principle* (§6.3).

Recovery preference order when a task needs state:

  1. **DP replica** — a healthy data-parallel peer already holds the full
     parameter/optimizer state; replicate over the interconnect.
  2. **In-memory checkpoint** — GEMINI-style host-RAM snapshot (local or
     ring neighbor).
  3. **Persistent checkpoint** — remote cloud filesystem, slowest tier.

``restore`` returns (state, source) so callers (and the simulator, which
charges per-tier costs) know which tier satisfied the request.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax

from repro import obs
from repro.checkpoint import inmemory, persistent


class CheckpointManager:
    def __init__(self, directory: str, n_ranks: int,
                 persist_every: int = 10, *, task: str):
        """``task`` is the task id keying the in-memory store: a manager
        serves exactly one training task, and the id must match what the
        coordinator/planner uses so ring snapshots survive handoffs
        between managers of the same task."""
        self.directory = directory
        self.store = inmemory.InMemoryStore(n_ranks)
        self.persist_every = persist_every
        self.task = task

    # ---- save path -------------------------------------------------------

    def save(self, rank: int, step: int, state: Any) -> None:
        """In-memory snapshot every call; async spool to persistent tier
        every ``persist_every`` steps (synchronous here; the simulator
        models the asynchrony).  The persistent tier writes the host
        snapshot, so the state crosses from the device once.  The span's
        ``copied_bytes`` are those the snapshot copied on the host after
        the transfer (``inmemory.copies``): all on the CPU, none from an
        accelerator."""
        with obs.span("ckpt.save", step=step) as sp:
            snap = self.store.put(self.task, rank, step, state)
            sizes = [x.nbytes for x in jax.tree.leaves(snap)]
            sp.attrs["bytes"] = sum(sizes)
            sp.attrs["copied_bytes"] = sum(
                n for x, n in zip(jax.tree.leaves(state), sizes)
                if inmemory.copies(x))
            if step % self.persist_every == 0:
                with obs.span("ckpt.persist", step=step):
                    persistent.save(self.directory, step, snap)

    # ---- restore path (nearest principle) ---------------------------------

    def restore(self, rank: int, like: Any,
                dp_peer_state: Optional[Any] = None,
                peer_step: Optional[int] = None) -> Tuple[Any, int, str]:
        """Returns (state, step, source).

        ``dp_peer_state`` is the live state of a healthy DP replica if one
        exists — the nearest source (the caller knows its peers; Unicron's
        coordinator passes it when replication is possible).
        """
        with obs.span("ckpt.restore") as sp:
            got = self._restore(rank, like, dp_peer_state, peer_step)
            sp.attrs["tier"] = got[2]
        return got

    def _restore(self, rank, like, dp_peer_state, peer_step):
        if dp_peer_state is not None:
            return dp_peer_state, int(peer_step or 0), "dp_replica"
        hit = self.store.get(self.task, rank)
        if hit is not None:
            step, snap, src = hit
            return snap, step, src
        step = persistent.latest_step(self.directory)
        if step is not None:
            return persistent.restore(self.directory, like, step), step, \
                "persistent"
        raise FileNotFoundError("no recovery source available")

    def drop_rank(self, rank: int) -> None:
        self.store.drop_rank(self.task, rank)
