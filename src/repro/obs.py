"""Spans inside the program.

``span(name, **attrs)`` times a block with ``time.perf_counter`` and
exposes ``t0``, ``t1`` and ``seconds`` once it exits, whether or not
anything is recorded, so callers that report a duration read it there.
While recording is on it also

- appends a ``Record(id, name, t0, t1, parent, attrs)`` to a bounded
  in-memory buffer (``CAP`` records; past that it counts ``dropped()``),
  ``parent`` being the id of the innermost recorded span open on the same
  thread;
- opens ``jax.profiler.TraceAnnotation(PREFIX + name)``, so that the span
  lies on the device trace's clock, on the host plane.

Recording is on exactly while a JAX profiler session collects host events:
whoever owns a trace turns it on by starting one.  Off, a span costs a look
at the profiler's state and two clock reads.  The buffer is never written
anywhere; ``records()`` hands out a copy and ``reset()`` empties it.
"""
from __future__ import annotations

import sys
import threading
import time
from itertools import count as _ids
from typing import Any, Dict, List, NamedTuple, Optional

#: most records the buffer holds
CAP = 1 << 17
#: prefix of the spans' names in a profiler trace
PREFIX = "unicron."


class Record(NamedTuple):
    id: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]
    attrs: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


_lock = threading.Lock()
_local = threading.local()
_next_id = _ids()
_records: List[Record] = []
_dropped = 0
_trace_active = None        # jax.profiler.TraceAnnotation.is_enabled


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def recording() -> bool:
    """Whether a JAX profiler session is collecting host events; never
    imports JAX (no session can run where nothing imported it)."""
    global _trace_active
    if _trace_active is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        _trace_active = jax.profiler.TraceAnnotation.is_enabled
    return _trace_active()


class Span:
    """One timed block; see the module's docstring."""
    __slots__ = ("name", "attrs", "t0", "t1", "id", "parent", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs
        self.t0 = self.t1 = 0.0
        self.id = self.parent = self._ann = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        if recording():
            st = _stack()
            self.id = next(_next_id)
            self.parent = st[-1].id if st else None
            st.append(self)
            self._ann = sys.modules["jax"].profiler.TraceAnnotation(
                PREFIX + self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        global _dropped
        self.t1 = time.perf_counter()
        if self.id is None:
            return
        self._ann.__exit__(*exc)
        self._ann = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        with _lock:
            if len(_records) < CAP:
                _records.append(Record(self.id, self.name, self.t0, self.t1,
                                       self.parent, self.attrs))
            else:
                _dropped += 1


def span(name: str, **attrs: Any) -> Span:
    return Span(name, attrs)


def reset() -> None:
    """Forget every record and the dropped count."""
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0


def records() -> List[Record]:
    with _lock:
        return list(_records)


def dropped() -> int:
    return _dropped
