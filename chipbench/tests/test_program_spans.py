"""CPU tests of the per-layer metrics that read the program's own spans
(``repro.obs``): each one's value on a made-up window of records, and
nothing (None) where the program has no ``repro.obs``, where its recorder
dropped records, or where the window holds no span of its kind.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import harness  # noqa: E402
from repro import obs  # noqa: E402

READERS = ["ckpt.d2h_s", "ckpt.host_copy_s", "loop.dispatch_ms",
           "planner.rows_ms", "planner.fetch_ms", "planner.unpack_ms",
           "planner.traceback_ms"]


def _records():
    """Two snapshots, two steps, two rebuilds and a dispatch, on a window
    [10, 100); the spans at 0 and 200 lie outside it."""
    out = []

    def add(name, t0, t1, parent=None, **attrs):
        rid = len(out) + 1
        out.append(obs.Record(rid, name, t0, t1, parent, attrs))
        return rid

    add("ckpt.save", 0.0, 5.0)
    add("ckpt.d2h", 1.0, 2.0, 1)                  # outside the window
    s = add("ckpt.save", 10.0, 20.0, step=8)
    add("ckpt.d2h", 10.0, 13.0, s)
    add("ckpt.host_copy", 13.0, 14.0, s)
    add("ckpt.d2h", 14.0, 15.0, s)
    add("ckpt.host_copy", 15.0, 16.5, s)
    s = add("ckpt.save", 30.0, 40.0, step=16)
    add("ckpt.d2h", 30.0, 32.0, s)
    add("ckpt.host_copy", 32.0, 35.0, s)
    for t, kind in ((50.0, "fused"), (55.0, "fused"), (60.0, "recovered")):
        st = add("loop.step", t, t + 4.0, kind=kind)
        add("loop.batch", t, t + 0.25, st)
        add("loop.dispatch", t + 0.25, t + 0.75 + (t - 50.0) / 10, st)
    for t in (70.0, 80.0):
        r = add("plan.rebuild", t, t + 8.0)
        tb = add("plan.table", t, t + 7.0, r)
        add("plan.rows", t, t + 1.0, tb)
        p = add("plan.program", t + 1.0, t + 4.0, tb)
        add("plan.program.wait", t + 1.0, t + 3.0, p)
        add("plan.fetch", t + 3.0, t + 4.0 - (t - 70.0) / 20, p)
        add("plan.unpack", t + 4.0, t + 4.5, tb)
        add("plan.traceback", t + 4.5, t + 6.0, tb)
    d = add("plan.dispatch", 90.0, 91.0)
    add("plan.traceback", 90.0, 91.0, d)
    add("plan.rebuild", 200.0, 201.0)
    return out


@pytest.fixture
def window(monkeypatch):
    monkeypatch.setattr(obs, "records", _records)
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    return harness.Run(cell={"name": "x"}, config={}, traffic={},
                       spans=harness.Spans(), window=(10.0, 100.0),
                       counters={})


@pytest.mark.parametrize("name,want", [
    ("ckpt.d2h_s", ((3 + 1) + 2) / 2),
    ("ckpt.host_copy_s", ((1 + 1.5) + 3) / 2),
    ("loop.dispatch_ms", 1e3 * ((0.25 + 0.5) + (0.25 + 1.0)) / 2),
    ("planner.rows_ms", 1e3 * 1.0),
    ("planner.fetch_ms", 1e3 * (1.0 + 0.5) / 2),
    ("planner.unpack_ms", 1e3 * 0.5),
    # every traceback in the window, the dispatch's too, per rebuild
    ("planner.traceback_ms", 1e3 * (1.5 + 1.5 + 1.0) / 2),
])
def test_reader_value(window, name, want):
    assert harness.load_reader(name)(window) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_the_program_spans(window, monkeypatch,
                                                        name):
    import repro
    read = harness.load_reader(name)
    with monkeypatch.context() as m:
        # a program without repro.obs, as at the parent of the change
        # that brought it
        m.setitem(sys.modules, "repro.obs", None)
        m.delattr(repro, "obs")
        assert read(window) is None
    assert read(window) is not None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert read(window) is None
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    monkeypatch.setattr(obs, "records", lambda: [])
    assert read(window) is None
