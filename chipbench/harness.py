"""What every cell's run shares: the chip check, the compile cache, the
benchmark's own spans around its calls into the program, the traced
window, the per-layer metric readers and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: everything a run writes (traces, compile cache) stays under here
STATE_DIR = os.path.join(ROOT, ".chipbench")
COMPILE_CACHE = os.path.join(STATE_DIR, "jax_cache")


class NoChip(SystemExit):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_bench() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve(bench: dict, workload: str) -> Tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of a cell, each found by the
    name ``BENCHMARK.json`` gives it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", name + ".py")


def load_module(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``, found by its name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"chipbench: no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The ``read(run)`` function of a per-layer metric's own file."""
    return load_module("metrics", name).read


def require_chips(n: int):
    """The devices, or exit non-zero without a result when JAX finds no
    TPU or fewer than ``n`` of them.  There is no CPU fallback."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"chipbench: no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: first device is {devs[0].platform!r}, "
                     f"not a TPU; there is no CPU fallback")
    if len(devs) < n:
        raise NoChip(f"chipbench: {n} chips asked for, {len(devs)} found")
    return devs[:n]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) through
    JAX's monitoring events; ``count`` is read around the window."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax
        self.count = 0

        def on_event(name, **kw):
            if name == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_listener(on_event)


@dataclass
class Spans:
    """The benchmark's own spans around its calls into the program, on the
    host clock; with ``annotate`` each is also a ``TraceAnnotation`` in
    the profiler's trace."""
    annotate: bool = False
    records: List[Tuple[str, float, float]] = field(default_factory=list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation("bench." + name)
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def of(self, name: str, lo: float, hi: float) -> List[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= lo and e <= hi + 1e-9]


def covered(intervals: List[Tuple[float, float]]) -> float:
    total, cur = 0.0, -math.inf
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        total += e - max(s, cur)
        cur = e
    return total


class Tracer:
    """The profiler around the measured window (``--trace 1``), writing
    under the checkout; python tracing off."""

    def __init__(self, enabled: bool, workload: str):
        self.enabled = enabled
        self.dir = os.path.join(STATE_DIR, "trace", workload)
        self.summary = None

    def start(self):
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1           # the benchmark's spans only
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if not self.enabled:
            return
        import jax
        from chipbench import trace
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        path = trace.find_xplane(self.dir)
        size = os.path.getsize(path)
        self.summary = trace.summarize(path)
        shutil.rmtree(self.dir, ignore_errors=True)
        print(f"trace: stop {t1 - t0:.1f} s, {size / 1e6:.1f} MB, reduce "
              f"{time.perf_counter() - t1:.1f} s", file=sys.stderr,
              flush=True)


def device_info(devs, summary=None) -> Dict[str, Any]:
    """The devices as JAX reports them: ``memory_peak_bytes`` is the
    fullest chip's ``peak_bytes_in_use``; beside it the fullest chip's
    ``peak_bytes_reserved``, the allocator's high-water mark, where the
    runtime reports one."""
    peak, reserved = 0, 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
    if reserved:
        info["memory_peak_reserved_bytes"] = reserved
    if summary is not None:
        info["busy_s"] = summary.busy_s
        info["window_s"] = summary.window_s
    return info


@dataclass
class Run:
    """What one run hands the per-layer metric readers."""
    cell: dict
    config: dict
    traffic: dict
    spans: Spans
    window: Tuple[float, float]               # host perf_counter
    counters: Dict[str, Any]
    summary: Any = None                        # trace.Summary, traced runs
    peak: Optional[Dict[str, float]] = None    # peaks.json row

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def span_times(self, name: str) -> List[float]:
        return self.spans.of(name, *self.window)

    def mean_span(self, name: str) -> Optional[float]:
        """Mean seconds of the benchmark's ``name`` spans in the window,
        or None where there is none."""
        times = self.span_times(name)
        return sum(times) / len(times) if times else None

    def idle_share(self) -> Optional[float]:
        """Share of the traced window, in %, in which no operation ran on
        the device; None without a trace."""
        s = self.summary
        if s is None or not s.trace.devices or s.window_s <= 0:
            return None
        return 100.0 * (1.0 - s.busy_s / s.window_s)


@dataclass
class Check:
    """One number compared for ``correct``, with its limit (pass when
    value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def per_layer(bench: dict, run: Run) -> Dict[str, Dict[str, Any]]:
    """The per-layer metrics this cell lists, each from its own reader;
    a reader that finds nothing returns None and the metric is left out."""
    out = {}
    cell = run.cell["name"]
    for m in bench["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(bench: dict, cell: dict,
               values: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics, as ``BENCHMARK.json`` lists them."""
    out = {}
    for m in bench["end_to_end"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


@dataclass
class Context:
    """One run's arguments and what the harness prepared for the cell's
    kind module (``chipbench/kinds/<kind>.py``)."""
    bench: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    limits: Dict[str, float]
    compiles: Any
    tracer: Any
    peak: Optional[Dict[str, float]] = None


def emit(result: Dict[str, Any], checks: List[Check]) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result["correct"] = bool(checks) and all(c.ok for c in checks)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    line = {k: result[k] for k in order if k in result}
    print(json.dumps(line, allow_nan=True), flush=True)
