"""Hierarchical checkpointing + nearest-principle state migration (§6.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.inmemory import InMemoryStore
from repro.checkpoint.manager import CheckpointManager
from repro.checkpoint import persistent
from repro.core.transition import (estimate_baseline, estimate_unicron,
                                   migrate_seconds, migration_source)


@pytest.fixture
def state():
    k = jax.random.PRNGKey(0)
    return {"w": jax.random.normal(k, (8, 8)),
            "b": jnp.arange(8, dtype=jnp.float32)}


def _close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y))


def test_persistent_roundtrip(tmp_path, state):
    persistent.save(str(tmp_path), 7, state)
    assert persistent.latest_step(str(tmp_path)) == 7
    got = persistent.restore(str(tmp_path), state)
    _close(got, state)


def test_latest_step_survives_torn_marker_and_tmp_leftovers(tmp_path,
                                                           state):
    """A crash between archive write and marker write (or mid-marker)
    must not lose the newest complete checkpoint (ISSUE 10 satellite)."""
    d = str(tmp_path)
    assert persistent.latest_step(d) is None          # empty directory
    persistent.save(d, 3, state)
    persistent.save(d, 12, state)

    # torn marker: empty file
    (tmp_path / "latest").write_text("")
    assert persistent.latest_step(d) == 12
    # torn marker: garbage bytes
    (tmp_path / "latest").write_text("12\x0034garbage")
    assert persistent.latest_step(d) == 12
    # marker points at a step whose archive never landed
    (tmp_path / "latest").write_text("99")
    assert persistent.latest_step(d) == 12
    # marker deleted entirely
    (tmp_path / "latest").unlink()
    assert persistent.latest_step(d) == 12

    # stray in-flight tmp archive from a dead writer is not a candidate
    (tmp_path / "ckpt_00000050.npz.tmp.npz").write_bytes(b"partial")
    (tmp_path / "ckpt_garbage.npz").write_bytes(b"junk")
    assert persistent.latest_step(d) == 12
    got = persistent.restore(d, state)
    _close(got, state)


def test_inmemory_ring_replication(state):
    store = InMemoryStore(n_ranks=4)
    store.put("t", 1, step=5, tree=state)
    step, snap, src = store.get("t", 1)
    assert (step, src) == (5, "inmemory_local")
    # rank 1's snapshot is replicated on neighbor rank 2
    store.drop_rank("t", 1)
    hit = store.get("t", 1)
    assert hit is not None and hit[2] == "inmemory_replica"
    _close(hit[1], state)


def test_cpu_snapshot_survives_a_donated_step(tmp_path, state):
    """On the CPU the transfer is a view of the device buffer: the
    snapshot copies it, so it holds no reference that keeps a step from
    donating the saved state, and the step leaves it as it was."""
    mgr = CheckpointManager(str(tmp_path), n_ranks=2, persist_every=100,
                            task="t")
    mgr.save(rank=0, step=1, state=state)
    want = {k: np.array(x) for k, x in state.items()}
    step = jax.jit(lambda s: jax.tree.map(lambda x: x * 3.0 + 1.0, s),
                   donate_argnums=0)
    new = jax.block_until_ready(step(state))
    assert all(x.is_deleted() for x in state.values())
    snap, at, src = mgr.restore(0, like=None)
    assert (at, src) == (1, "inmemory_local")
    for k, x in want.items():
        assert snap[k].tobytes() == x.tobytes()
    assert not np.array_equal(np.asarray(new["b"]), want["b"])


def test_nearest_principle_ordering(tmp_path, state):
    """DP replica beats in-memory beats persistent."""
    mgr = CheckpointManager(str(tmp_path), n_ranks=4, persist_every=1,
                            task="gpt-7b")
    mgr.save(rank=0, step=3, state=state)
    # keyed by the real task id, not a hardcoded constant
    assert mgr.store.get("gpt-7b", 0) is not None
    assert mgr.store.get("task", 0) is None

    peer = jax.tree.map(lambda x: x + 1, state)
    got, step, src = mgr.restore(0, state, dp_peer_state=peer, peer_step=4)
    assert src == "dp_replica" and step == 4
    _close(got, peer)

    got, step, src = mgr.restore(0, state)
    assert src == "inmemory_local" and step == 3

    mgr.store.drop_rank(mgr.task, 0)
    mgr.store.drop_rank(mgr.task, mgr.store.neighbor(0))
    got, step, src = mgr.restore(0, state)
    assert src == "persistent" and step == 3
    _close(got, state)


def test_restore_without_any_source(tmp_path, state):
    mgr = CheckpointManager(str(tmp_path), n_ranks=2, task="empty")
    with pytest.raises(FileNotFoundError):
        mgr.restore(0, state)


def test_migration_source_selection():
    assert migration_source(dp_degree=4, inmemory_available=False) == \
        "dp_replica"
    assert migration_source(dp_degree=1, inmemory_available=True) == \
        "inmemory"
    assert migration_source(dp_degree=1, inmemory_available=False) == \
        "persistent"


def test_migrate_seconds_tier_ordering():
    b = 100e9
    assert migrate_seconds(b, "dp_replica") < migrate_seconds(b, "inmemory")
    assert migrate_seconds(b, "inmemory") <= migrate_seconds(b, "persistent")


def test_transition_cost_figure9_ordering():
    """Unicron < Oobleck/Bamboo (dynamic reconfig) < Megatron/Varuna
    (checkpoint restart) — Fig. 9's qualitative result."""
    state_bytes = 16.0 * 7e9            # GPT-3 7B
    uni = estimate_unicron(state_bytes, avg_iter_s=30.0, dp_degree=4,
                           detect_s=1.8)
    dyn = estimate_baseline(state_bytes, detect_s=1800.0,
                            dynamic_reconfig=True, ckpt_restart=False)
    ckpt = estimate_baseline(state_bytes, detect_s=1800.0,
                             dynamic_reconfig=False, ckpt_restart=True)
    assert uni.total < dyn.total < ckpt.total
    # paper figure-2 magnitude: baseline restart ~ an hour
    assert ckpt.total > 45 * 60


def test_unicron_partial_result_recompute_bounded():
    """Partial-result reuse keeps recompute below one iteration."""
    c = estimate_unicron(1e9, avg_iter_s=60.0, dp_degree=8, detect_s=0.3)
    assert c.recompute_s <= 60.0


# ---- GEMINI preference order through the agent recovery path (§6.3) -------


def test_agent_recovers_local_first(state):
    from repro.core.agent import UnicronAgent
    store = InMemoryStore(n_ranks=4)
    store.put("t", 1, step=9, tree=state)
    agent = UnicronAgent(1, None, n_gpus=4)     # kv unused on this path
    got, step, src = agent.recover_checkpoint(store, "t", 1)
    assert (step, src) == (9, "inmemory_local")
    _close(got, state)


def test_agent_recovers_neighbor_replica_then_persistent(tmp_path, state):
    from repro.checkpoint import persistent as pt
    from repro.core.agent import UnicronAgent
    store = InMemoryStore(n_ranks=4)
    store.put("t", 1, step=9, tree=state)
    pt.save(str(tmp_path), 7, state)
    agent = UnicronAgent(1, None, n_gpus=4)
    # host 1 dies: its local copy is gone, neighbor (rank 2) holds it
    store.drop_rank("t", 1)
    got, step, src = agent.recover_checkpoint(store, "t", 1,
                                              persist_dir=str(tmp_path))
    assert (step, src) == (9, "inmemory_replica")
    _close(got, state)
    # neighbor also lost: only the persistent tier remains (older step)
    store.drop_rank("t", store.neighbor(1))
    got, step, src = agent.recover_checkpoint(store, "t", 1,
                                              persist_dir=str(tmp_path),
                                              template=state)
    assert (step, src) == (7, "persistent")
    _close(got, state)


def test_agent_recover_no_tier_raises(state):
    from repro.core.agent import UnicronAgent
    agent = UnicronAgent(0, None, n_gpus=4)
    with pytest.raises(FileNotFoundError):
        agent.recover_checkpoint(InMemoryStore(n_ranks=2), "t", 0)


def test_drop_rank_hosting_anothers_replica(state):
    """Losing host 2 also loses rank *1*'s replica (held ON host 2), but
    rank 1 still recovers from its own local copy; rank 2 recovers from
    its replica on host 3."""
    store = InMemoryStore(n_ranks=4)
    store.put("t", 1, step=5, tree=state)       # replica lands on host 2
    store.put("t", 2, step=6, tree=state)       # replica lands on host 3
    store.drop_rank("t", 2)
    hit1 = store.get("t", 1)
    assert hit1 is not None and hit1[2] == "inmemory_local"
    hit2 = store.get("t", 2)
    assert hit2 is not None and hit2[2] == "inmemory_replica"
    # now rank 1's host dies too: local gone AND its replica died with
    # host 2 earlier -> nothing left for rank 1
    store.drop_rank("t", 1)
    assert store.get("t", 1) is None


# ---- bf16 state through both tiers (full-width param_dtype) ----------------


def _bits_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x.reshape(-1).view(np.uint8),
                              y.reshape(-1).view(np.uint8))


@pytest.fixture
def bf16_train_state():
    """A train state with bf16 params and f32 master/moments: the layout
    of a config at its published ``param_dtype``."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models.model import build_model
    from repro.optim import AdamW, constant
    from repro.train.state import init_train_state
    cfg = dataclasses.replace(get_arch("qwen3-4b").reduced(),
                              param_dtype="bfloat16")
    state = init_train_state(build_model(cfg), AdamW(lr=constant(1e-3)),
                             jax.random.PRNGKey(0))
    assert jax.tree.leaves(state.params)[0].dtype == jnp.bfloat16
    return state


def test_bf16_state_roundtrips_persistent_bitwise(tmp_path, bf16_train_state):
    persistent.save(str(tmp_path), 4, bf16_train_state)
    _bits_equal(persistent.restore(str(tmp_path), bf16_train_state),
                bf16_train_state)


def test_bf16_state_restores_from_both_tiers_bitwise(tmp_path,
                                                    bf16_train_state):
    mgr = CheckpointManager(str(tmp_path), n_ranks=2, persist_every=2,
                            task="bf16")
    mgr.save(rank=0, step=2, state=bf16_train_state)
    got, step, src = mgr.restore(0, bf16_train_state)
    assert (step, src) == (2, "inmemory_local")
    _bits_equal(got, bf16_train_state)
    mgr.drop_rank(0)
    mgr.drop_rank(mgr.store.neighbor(0))
    got, step, src = mgr.restore(0, bf16_train_state)
    assert (step, src) == (2, "persistent")
    _bits_equal(got, bf16_train_state)
