"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

- device busy time: the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane),
  clipped to the measured window;
- per-operation and per-program device time (``XLA Ops`` and
  ``XLA Modules`` lines);
- idle gaps on the device, each attributed to the benchmark span that was
  open on the host at the gap's midpoint (the innermost one).

Host spans are the benchmark's ``jax.profiler.TraceAnnotation``s, whose
names start with ``SPAN_PREFIX``; they sit on the ``/host:CPU`` plane, on
the same clock as the device events.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no benchmark span)"

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Tuple[int, int]:
        """The ``bench.window`` span, else the extent of all events."""
        for name, s, e in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        evs = [ev for d in self.ops.values() for ev in d] + self.spans
        return min(ev[1] for ev in evs), max(ev[2] for ev in evs)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[dev] = _events(line)
                elif line.name == MODULES_LINE:
                    tr.modules[dev] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                tr.spans += [ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX)]
    tr.spans.sort(key=lambda ev: ev[1])
    return tr


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        s = int(e.start_ns)
        out.append((e.name, s, s + int(e.duration_ns)))
    return out


def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals covered by the events."""
    out: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace, dev: int, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(clip(tr.ops.get(dev, []), lo, hi)))


def idle_gaps(tr: Trace, dev: int, lo: int,
              hi: int) -> List[Tuple[int, int]]:
    gaps, cur = [], lo
    for s, e in union(clip(tr.ops.get(dev, []), lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def span_at(spans: Sequence[Event], t: int) -> str:
    """The innermost benchmark span open at ``t`` (the window span only
    when nothing inside it is open)."""
    best: Optional[Event] = None
    for ev in spans:
        if ev[1] <= t < ev[2] and (best is None or ev[1] >= best[1]):
            best = ev
    return NO_SPAN if best is None else best[0]


def idle_by_span(tr: Trace, dev: int, lo: int, hi: int) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s, e in idle_gaps(tr, dev, lo, hi):
        name = span_at(tr.spans, (s + e) // 2)
        out[name] = out.get(name, 0) + (e - s)
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction name of an ``XLA Ops`` event
    (``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``)."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def module_name(event_name: str) -> str:
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0]


def select(events: Sequence[Event], pred: Callable[[str], bool],
           lo: int, hi: int) -> Tuple[int, int]:
    """(total device ns, count) of the events whose name satisfies
    ``pred`` and that start inside [lo, hi)."""
    ns = n = 0
    for name, s, e in events:
        if lo <= s < hi and pred(name):
            ns += e - s
            n += 1
    return ns, n


def top_ops(tr: Trace, dev: int, lo: int, hi: int,
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` instructions with the most device time, in seconds."""
    tot: Dict[str, int] = {}
    for name, s, e in clip(tr.ops.get(dev, []), lo, hi):
        key = op_name(name)
        tot[key] = tot.get(key, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns * 1e-9) for name, ns in best]


@dataclass
class Summary:
    """What one traced window reduces to (all times in seconds)."""
    trace: Trace
    lo: int
    hi: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        devs = self.trace.devices
        if not devs:
            return 0.0
        return sum(busy_ns(self.trace, d, self.lo, self.hi)
                   for d in devs) / len(devs) * 1e-9

    def ops(self, pred: Callable[[str], bool]) -> Tuple[float, int]:
        """Device seconds and count of the matching ops, summed over
        devices."""
        ns = n = 0
        for d in self.trace.devices:
            a, b = select(self.trace.ops[d], pred, self.lo, self.hi)
            ns, n = ns + a, n + b
        return ns * 1e-9, n

    def modules(self, pred: Callable[[str], bool]) -> Tuple[float, int]:
        ns = n = 0
        for d in self.trace.modules:
            a, b = select(self.trace.modules[d],
                          lambda nm: pred(module_name(nm)), self.lo, self.hi)
            ns, n = ns + a, n + b
        return ns * 1e-9, n

    def breakdown(self, k: int = 10) -> Dict[str, List[Tuple[str, float]]]:
        devs = self.trace.devices
        if not devs:
            return {"device_ops": [], "idle_gaps": []}
        dev = devs[0]
        gaps = idle_by_span(self.trace, dev, self.lo, self.hi)
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": top_ops(self.trace, dev, self.lo, self.hi, k),
                "idle_gaps": [(name, ns * 1e-9) for name, ns in idle]}


def summarize(path: str) -> Summary:
    tr = load(path)
    lo, hi = tr.window()
    return Summary(tr, lo, hi)
