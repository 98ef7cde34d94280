"""The program's own spans (``repro.obs``) inside a run's window, for the
per-layer metrics that read them.  The program records them while the
profiler traces the window, on the host clock the window is taken on.

There is nothing to read (None) on a program without ``repro.obs``, when
its recorder dropped records, or when the window holds no such span.
"""
from __future__ import annotations

from typing import Iterable, List, Optional


def in_window(run) -> Optional[List]:
    """The program's span records that lie inside the window."""
    try:
        from repro import obs
    except ImportError:
        return None
    if obs.dropped():
        return None
    lo, hi = run.window
    return [r for r in obs.records() if r.t0 >= lo and r.t1 <= hi + 1e-9]


def per_parent(run, parent: str, children: Iterable[str], under: bool = True,
               **attrs) -> Optional[float]:
    """Seconds of the ``children`` spans per ``parent`` span in the window,
    mean over the parents whose attributes include ``attrs``.  With
    ``under`` only children nested (at any depth) in such a parent count;
    without, every child span in the window does."""
    recs = in_window(run)
    if recs is None:
        return None
    children = set(children)
    by_id = {r.id: r for r in recs}
    tops = {r.id for r in recs if r.name == parent
            and all(r.attrs.get(k) == v for k, v in attrs.items())}
    total, found = 0.0, False
    for r in recs:
        if r.name not in children:
            continue
        if under:
            up = by_id.get(r.parent)
            while up is not None and up.id not in tops:
                up = by_id.get(up.parent)
            if up is None:
                continue
        total += r.seconds
        found = True
    if not tops or not found:
        return None
    return total / len(tops)
