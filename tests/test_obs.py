"""The program's spans (``repro.obs``): what a span records off and on,
the buffer's bound, and the span trees of the managed loop, the checkpoint
save, the SEV2 iteration, the control loop and the plan rebuild."""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.checkpoint import inmemory
from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch
from repro.core.agent import UnicronAgent
from repro.core.cluster import Cluster
from repro.core.controlloop import ControlLoop
from repro.core.coordinator import UnicronCoordinator
from repro.core.costmodel import A800, TaskModel
from repro.core.kvstore import KVStore
from repro.core.resumption import run_iteration_with_failure
from repro.core.waf import Task
from repro.launch.train import build_job, run


@pytest.fixture
def rec(tmp_path):
    """A profiler session for the test, which turns recording on, and
    nothing left behind after it."""
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield obs
    finally:
        jax.profiler.stop_trace()
        obs.reset()


@pytest.fixture
def annotations(monkeypatch):
    """The names of the profiler annotations spans open."""
    opened = []

    class Annotation:
        is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return opened


def _tree(records):
    """(name, parent's name) -> count."""
    by_id = {r.id: r for r in records}
    return collections.Counter(
        (r.name, by_id[r.parent].name if r.parent in by_id else None)
        for r in records)


def test_span_off_records_nothing_but_times(annotations):
    obs.reset()
    assert not obs.recording()
    with obs.span("outer", step=1) as sp:
        with obs.span("inner"):
            pass
    assert sp.seconds > 0 and sp.t1 > sp.t0
    assert obs.records() == []
    assert annotations == []


def test_span_on_links_parents_and_keeps_attrs(rec, annotations):
    with obs.span("a", step=3) as a:
        with obs.span("b") as b:
            with obs.span("c", rank=1):
                pass
            b.attrs["bytes"] = 7
        with obs.span("b"):
            pass
    recs = {r.id: r for r in obs.records()}
    assert _tree(recs.values()) == {("a", None): 1, ("b", "a"): 2,
                                    ("c", "b"): 1}
    assert recs[a.id].attrs == {"step": 3}
    assert recs[b.id].attrs == {"bytes": 7}
    assert recs[a.id].t0 == a.t0 and recs[a.id].seconds == a.seconds
    assert annotations == [obs.PREFIX + n for n in "abcb"]


def test_buffer_is_bounded_and_counts_what_it_drops(rec, monkeypatch):
    monkeypatch.setattr(obs, "CAP", 5)
    for i in range(8):
        with obs.span("s", i=i) as sp:
            pass
        assert sp.seconds >= 0
    assert [r.attrs["i"] for r in obs.records()] == [0, 1, 2, 3, 4]
    assert obs.dropped() == 3
    obs.reset()
    assert obs.records() == [] and obs.dropped() == 0


def test_recording_follows_a_profiler_session(tmp_path):
    """Spans record exactly while a JAX profiler session collects host
    events."""
    obs.reset()
    with obs.span("before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.recording()
        with obs.span("during"):
            pass
    finally:
        jax.profiler.stop_trace()
    with obs.span("after"):
        pass
    assert not obs.recording()
    assert [r.name for r in obs.records()] == ["during"]
    obs.reset()


def test_split_snapshot_is_np_array_and_owns_its_memory(rec):
    tree = {"w": jnp.arange(24, dtype=jnp.bfloat16).reshape(4, 6),
            "s": jnp.int32(7), "h": np.ones(3, np.float32)}
    snap = inmemory._snapshot(tree)
    for k, x in tree.items():
        want = np.array(x)
        got = snap[k]
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        assert got.flags.owndata and got.flags.writeable
        assert not np.shares_memory(got, np.asarray(x))
    assert _tree(obs.records()) == {("ckpt.d2h", None): 3,
                                    ("ckpt.host_copy", None): 3}


class _DeviceLeaf(jax.Array):
    """A stand-in for an array on an accelerator whose transfer makes
    ``host``."""

    def __init__(self, host):
        self.host = host

    def devices(self):
        return {type("Device", (), {"platform": "tpu"})()}

    def __array__(self, dtype=None, copy=None):
        return self.host


def test_snapshot_keeps_an_accelerator_leafs_transfer(rec, tmp_path):
    host = np.arange(12, dtype=np.float32).reshape(3, 4)
    tree = {"w": _DeviceLeaf(host), "h": np.ones(5, np.float32)}
    mgr = CheckpointManager(str(tmp_path), n_ranks=2, persist_every=100,
                            task="t")
    mgr.save(rank=0, step=1, state=tree)
    snap = mgr.store.get("t", 0)[1]
    assert snap["w"] is host
    assert snap["h"] is not tree["h"]
    assert not np.shares_memory(snap["h"], tree["h"])
    assert _tree(obs.records()) == {("ckpt.save", None): 1,
                                    ("ckpt.d2h", "ckpt.save"): 2,
                                    ("ckpt.host_copy", "ckpt.save"): 1,
                                    ("ckpt.release", "ckpt.save"): 1}
    (save,) = [r for r in obs.records() if r.name == "ckpt.save"]
    assert save.attrs == {"step": 1, "bytes": host.nbytes + 20,
                          "copied_bytes": 20}
    assert not inmemory.copies(tree["w"])
    assert inmemory.copies(tree["h"]) and inmemory.copies(jnp.ones(2))


def _coordinator(**kw):
    tasks = [Task(model=TaskModel.from_arch(get_arch(a), global_batch=64))
             for a in ("gpt3-1.3b", "gpt3-7b")]
    return UnicronCoordinator(tasks, [32, 96], A800, kv=KVStore(),
                              n_cluster_workers=128, **kw)


@pytest.mark.parametrize("engine", ["batched", "fused"])
def test_plan_stats_read_the_rebuild_and_dispatch_spans(rec, engine):
    coord = _coordinator(plan_engine=engine, prebuild_scenarios=True)
    obs.reset()
    before = coord.plan_stats.table_rebuild_s
    coord.refresh_plan_table()
    recs = obs.records()
    (rebuild,) = [r for r in recs if r.name == "plan.rebuild"]
    assert coord.plan_stats.table_rebuild_s - before == rebuild.seconds
    tree = _tree(recs)
    assert tree[("plan.table", "plan.rebuild")] == 1
    if engine == "fused":
        for child in ("plan.rows", "plan.program", "plan.unpack"):
            assert tree[(child, "plan.table")] == 1
        for child in ("plan.program.wait", "plan.fetch"):
            assert tree[(child, "plan.program")] == 1
    # the eager table traces back every scenario (2 faults, 2 finishes,
    # one join) inside its rebuild
    assert tree[("plan.traceback", "plan.table")] == 5
    obs.reset()
    plan, hit = coord.plan_for(120, 0, "fault:0")
    (dispatch,) = obs.records()
    assert hit and dispatch.name == "plan.dispatch"
    assert dispatch.attrs == {"hit": True}
    assert coord.plan_stats.last_dispatch_s == dispatch.seconds
    obs.reset()
    plan, hit = coord.plan_for(120, 0, None)
    tree = _tree(obs.records())
    assert not hit and tree[("plan.solve", "plan.dispatch")] == 1


def test_tick_records_its_children_and_each_handled_event(rec):
    coord = _coordinator()
    cluster = Cluster(n_nodes=16, gpus_per_node=8)
    cluster.assign([32, 96])
    agents = {i: UnicronAgent(i, coord.kv) for i in range(16)}
    loop = ControlLoop(coord, cluster, agents)
    for a in agents.values():
        a.heartbeat(now=0.0)
    agents[5].kill()
    for a in agents.values():
        a.heartbeat(now=4.0)
    obs.reset()
    (ev,) = loop.tick(now=8.0)                   # node 5's lease lapsed
    recs = obs.records()
    tree = _tree(recs)
    assert tree[("ctrl.tick", None)] == 1
    for child, n in (("ctrl.expire", 1), ("ctrl.drain", 3), ("ctrl.gc", 1),
                     ("ctrl.handle", 1)):
        assert tree[(child, "ctrl.tick")] == n
    assert tree[("plan.dispatch", "ctrl.handle")] == 1
    assert tree[("plan.rebuild", "ctrl.handle")] == 1
    (handle,) = [r for r in recs if r.name == "ctrl.handle"]
    assert handle.attrs["node"] == 5 == ev.node
    assert handle.attrs["kind"] == ev.kind.value
    assert handle.attrs["case"].startswith("5:")


def test_run_records_the_managed_loop_tree(rec, tmp_path):
    """Two fused steps, a save after the second, then the SEV2 step."""
    cfg = dataclasses.replace(get_arch("qwen3-4b").reduced(),
                              param_dtype="bfloat16")
    job, state = build_job(cfg, seq=32, batch=4, n_micro=4, dp=2, lr=1e-3,
                           total_steps=20, ckpt_dir=str(tmp_path),
                           ckpt_every=2)
    obs.reset()
    state, recs = run(job, state, 3, inject_fail=2, log=lambda s: None)
    spans = obs.records()
    n_leaves = len(jax.tree.leaves(state))
    assert _tree(spans) == {
        ("loop.step", None): 3,
        ("loop.batch", "loop.step"): 2,
        ("loop.dispatch", "loop.step"): 2,
        ("loop.sync", "loop.step"): 3,
        ("loop.monitor", "loop.step"): 2,
        ("ckpt.save", "loop.step"): 1,
        ("ckpt.d2h", "ckpt.save"): n_leaves,
        ("ckpt.host_copy", "ckpt.save"): n_leaves,
        ("ckpt.release", "ckpt.save"): 1,
        ("ckpt.persist", "ckpt.save"): 1,
        ("sev2.iteration", "loop.step"): 1,
        ("sev2.grads", "sev2.iteration"): 4,
        ("sev2.allreduce", "sev2.iteration"): 1,
        ("sev2.optimizer", "sev2.iteration"): 1,
    }
    steps = [r for r in spans if r.name == "loop.step"]
    assert [(r.attrs["step"], r.attrs["kind"]) for r in steps] == [
        (0, "fused"), (1, "fused"), (2, "recovered")]
    sync = {r.attrs["step"]: r for r in spans if r.name == "loop.sync"}
    for r, s in zip(recs, steps):
        assert r["seconds"] == sync[r["step"]].t1 - s.t0
    (save,) = [r for r in spans if r.name == "ckpt.save"]
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    assert save.attrs == {"step": 2, "bytes": n_bytes,
                          "copied_bytes": n_bytes}
    # rank 1 of 2 dies before its first micro-batch: rank 0 takes its two,
    # which nobody had computed
    grads = [r.attrs for r in spans if r.name == "sev2.grads"]
    assert sorted((g["rank"], g["mb"], g["redone"]) for g in grads) == [
        (0, 0, False), (0, 1, False), (0, 2, False), (0, 3, False)]


@pytest.mark.parametrize("fail_after_mb,want", [
    (0, [(0, 0, False), (0, 1, False), (0, 2, False), (0, 3, False)]),
    (1, [(0, 0, False), (0, 1, False), (0, 2, True), (0, 3, False),
         (1, 2, False)]),
    (2, [(0, 0, False), (0, 1, False), (0, 2, True), (0, 3, True),
         (1, 2, False), (1, 3, False)]),
])
def test_sev2_grads_mark_only_lost_work_redone(rec, fail_after_mb, want):
    """A micro-batch is redone when the failed rank had computed it before
    it died; the ones it never started are not."""
    def grad_fn(params, batch):
        return {"w": params["w"] * batch}, None

    total, n = run_iteration_with_failure(
        grad_fn, {"w": jnp.float32(1.0)}, lambda mb: jnp.float32(mb + 1),
        n_ranks=2, n_micro=4, fail_rank=1, fail_after_mb=fail_after_mb)
    assert n == 4 and float(total["w"]) == 1 + 2 + 3 + 4
    recs = obs.records()
    grads = [r.attrs for r in recs if r.name == "sev2.grads"]
    assert sorted((g["rank"], g["mb"], g["redone"]) for g in grads) == want
    assert _tree(recs)[("sev2.allreduce", None)] == 1
