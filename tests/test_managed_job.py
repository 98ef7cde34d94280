"""The managed training loop of ``launch.train`` (the CLI's and the chip
smoke run's path) at a small size: donated fused steps, saves to both
checkpoint tiers, and the injected SEV2 iteration."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.train import build_job, run
from repro.models.model import build_model
from repro.train.state import init_train_state
from repro.train.step import make_train_step


def _cfg():
    return dataclasses.replace(get_arch("qwen3-4b").reduced(),
                               param_dtype="bfloat16")


@pytest.fixture
def job_state(tmp_path):
    return build_job(_cfg(), seq=32, batch=4, n_micro=4, dp=2, lr=1e-3,
                     total_steps=20, ckpt_dir=str(tmp_path), ckpt_every=2)


def _bits(tree):
    return [np.asarray(x).reshape(-1).view(np.uint8)
            for x in jax.tree.leaves(tree)]


def test_donated_step_matches_undonated(job_state):
    """The job's step donates its input state and returns the same state
    and metrics, bit for bit, as the step without donation."""
    job, state = job_state
    model = build_model(job.cfg)
    ref_state = init_train_state(model, job.opt, jax.random.PRNGKey(0))
    plain = jax.jit(make_train_step(model, job.opt, job.n_micro))
    for step in range(2):
        ref_state, ref_m = plain(ref_state, job.batch(step))
        old = state
        state, m = job.step(state, step)
        assert all(x.is_deleted() for x in jax.tree.leaves(old))
        assert float(m["loss"]) == float(ref_m["loss"])
        assert float(m["grad_norm"]) == float(ref_m["grad_norm"])
    for a, b in zip(_bits(state), _bits(ref_state)):
        assert np.array_equal(a, b)


def test_run_saves_both_tiers_and_recovers_sev2(job_state):
    job, state = job_state
    state, recs = run(job, state, 4, inject_fail=2, log=lambda s: None)
    assert [r["kind"] for r in recs] == ["fused", "fused", "recovered",
                                         "fused"]
    assert [r["saved"] for r in recs] == [False, True, False, True]
    assert all(np.isfinite(r["loss"]) for r in recs if r["loss"] is not None)
    assert int(state.step) == 4
    got, step, src = job.mgr.restore(0, state)
    assert (step, src) == (4, "inmemory_local")
    job.mgr.drop_rank(0)
    job.mgr.drop_rank(job.mgr.store.neighbor(0))
    got, step, src = job.mgr.restore(0, state)
    assert (step, src) == (4, "persistent")
    for a, b in zip(_bits(got), _bits(state)):
        assert np.array_equal(a, b)


def test_recovered_gradient_equals_fault_free(job_state):
    """Eq. 7: rank 1 dies before finishing a micro-batch; rank 0 absorbs
    its micro-batches and the gradient sum is the fault-free one up to
    f32 summation order."""
    job, state = job_state
    want, n = job.iteration_grads(state.params, 0)
    for fail_after in (0, 1):
        got, n2 = job.iteration_grads(state.params, 0, fail_rank=1,
                                      fail_after_mb=fail_after)
        assert n2 == n
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
