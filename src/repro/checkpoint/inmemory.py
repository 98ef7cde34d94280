"""GEMINI-style in-memory checkpointing [SOSP'23, ref 49 in the paper].

Each agent keeps the latest training state snapshot in host CPU RAM and
*replicates it to a neighbor host* (ring placement), so that when a node
fails, its state is recoverable from the neighbor's RAM instead of remote
storage.  Unicron's agent manages this store and asynchronously spools
snapshots to the persistent tier (checkpoint.persistent).

This module implements the functional store; the cluster simulator charges
the paper-calibrated bandwidths for each tier.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro import obs


def copies(x: Any) -> bool:
    """Whether the snapshot copies ``x``'s host array after the transfer.

    A ``jax.Array`` on an accelerator transfers into a fresh host array
    the runtime makes for that transfer, which nothing else writes: the
    snapshot keeps it.  On a CPU device the transfer is a view of the
    device buffer (and ``jax.device_put`` of a numpy array may alias it
    in turn), memory that a donated step consumes: a snapshot holding it
    would share it with the live state and keep the step from donating
    it.  A numpy or Python leaf is the caller's own object.  Those two
    are copied."""
    return not (isinstance(x, jax.Array)
                and all(d.platform != "cpu" for d in x.devices()))


def _leaf_to_host(x: Any) -> np.ndarray:
    """The device-to-host transfer into a host array and, where
    ``copies(x)``, the copy of it into an array the snapshot owns."""
    with obs.span("ckpt.d2h"):
        host = np.asarray(x)
    if not copies(x):
        return host
    with obs.span("ckpt.host_copy"):
        return np.array(host)


def _snapshot(tree: Any) -> Any:
    """Copy a pytree to host memory (numpy).  CPU and numpy leaves are
    copied after the transfer, since their host array may alias memory
    that a donated step consumes; a leaf from an accelerator keeps the
    fresh host array of its transfer, which JAX marks read-only.  So
    snapshot leaves may be read-only: nothing writes into a snapshot (the
    persistent tier, restore and ``jax.device_put`` only read it)."""
    return jax.tree.map(_leaf_to_host, tree)


class InMemoryStore:
    """Ring-replicated host-RAM checkpoint store.

    Keyed by (task_id, rank).  ``put`` stores the snapshot locally and on
    the ring neighbor; ``get`` implements the recovery preference:
    local copy -> neighbor replica.
    """

    def __init__(self, n_ranks: int):
        self.n_ranks = n_ranks
        self._local: Dict[Tuple[str, int], Tuple[int, Any]] = {}
        self._replica: Dict[Tuple[str, int], Tuple[int, Any]] = {}

    def neighbor(self, rank: int) -> int:
        return (rank + 1) % self.n_ranks

    def put(self, task: str, rank: int, step: int, tree: Any) -> Any:
        """Store a host snapshot of ``tree``; returns the snapshot."""
        snap = _snapshot(tree)
        # the previous snapshot's host arrays are freed here, with the
        # last reference to them
        with obs.span("ckpt.release"):
            self._local[(task, rank)] = (step, snap)
            self._replica[(task, self.neighbor(rank))] = (step, snap)
        return snap

    def drop_rank(self, task: str, rank: int) -> None:
        """Simulate host loss: local copy and any replica *held on* the
        failed host vanish."""
        self._local.pop((task, rank), None)
        self._replica.pop((task, rank), None)

    def get(self, task: str, rank: int) -> Optional[Tuple[int, Any, str]]:
        """Returns (step, snapshot, source) or None."""
        if (task, rank) in self._local:
            s, t = self._local[(task, rank)]
            return s, t, "inmemory_local"
        if (task, self.neighbor(rank)) in self._replica:
            s, t = self._replica[(task, self.neighbor(rank))]
            return s, t, "inmemory_replica"
        return None

    def available(self, task: str, rank: int) -> bool:
        return self.get(task, rank) is not None
