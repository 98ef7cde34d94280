"""Persistent (remote-storage) checkpointing.

Pytrees are flattened to path-keyed npz archives.  In the paper's setting
this is the cloud filesystem tier (20 GB/s); the simulator charges that
bandwidth, while this module provides the real functional store used by
examples and tests.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import numpy as np

# npz has no code for the ml_dtypes (bfloat16, float8, ...): their
# numpy dtype kind is "V", which ``np.load`` hands back as raw void
# bytes.  Such leaves are stored bit for bit as unsigned integers of the
# same width, and this entry maps each of their keys to its dtype name.
_DTYPES_KEY = "__dtypes__"


def _flatten(tree) -> dict:
    flat, dtypes = {}, {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(path)
        arr = np.asarray(leaf)
        if arr.dtype.kind == "V":
            dtypes[key] = arr.dtype.name
            arr = arr.view(f"u{arr.dtype.itemsize}")
        flat[key] = arr
    flat[_DTYPES_KEY] = np.asarray(json.dumps(dtypes))
    return flat


def _treedef_of(tree):
    return jax.tree_util.tree_structure(tree)


def save(directory: str, step: int, tree: Any) -> str:
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"ckpt_{step:08d}.npz")
    tmp = path + ".tmp.npz"
    np.savez(tmp, **_flatten(tree))
    os.replace(tmp, path)
    with open(os.path.join(directory, "latest"), "w") as f:
        f.write(str(step))
    return path


def _scan_steps(directory: str) -> Optional[int]:
    """Newest complete archive on disk, ignoring in-flight ``.tmp.npz``
    leftovers from a writer that died mid-``save``."""
    best = None
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    for name in names:
        if not name.startswith("ckpt_") or not name.endswith(".npz"):
            continue
        if name.endswith(".tmp.npz"):
            continue
        stem = name[len("ckpt_"):-len(".npz")]
        if not stem.isdigit():
            continue
        step = int(stem)
        if best is None or step > best:
            best = step
    return best


def latest_step(directory: str) -> Optional[int]:
    """Crash-safe: the ``latest`` marker is written non-atomically after
    the archive, so a crash can leave it torn, empty, or pointing at a
    step whose archive never landed.  Any of those falls back to
    scanning for the newest complete archive."""
    marker = os.path.join(directory, "latest")
    step = None
    try:
        with open(marker) as f:
            step = int(f.read().strip())
    except (OSError, ValueError):
        step = None
    if step is not None and os.path.exists(
            os.path.join(directory, f"ckpt_{step:08d}.npz")):
        return step
    return _scan_steps(directory)


def restore(directory: str, like: Any, step: Optional[int] = None) -> Any:
    """Restore into the structure (and dtypes) of ``like``."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {directory}")
    path = os.path.join(directory, f"ckpt_{step:08d}.npz")
    leaves_with_path = jax.tree_util.tree_flatten_with_path(like)[0]
    new_leaves = []
    with np.load(path) as data:
        dtypes = (json.loads(str(data[_DTYPES_KEY]))
                  if _DTYPES_KEY in data.files else {})
        for p, leaf in leaves_with_path:
            key = jax.tree_util.keystr(p)
            arr = data[key]
            if key in dtypes:
                arr = arr.view(jax.numpy.dtype(dtypes[key]))
            new_leaves.append(arr.astype(leaf.dtype)
                              if hasattr(leaf, "dtype") else arr)
    return jax.tree_util.tree_unflatten(_treedef_of(like), new_leaves)


def checkpoint_nbytes(tree: Any) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))
