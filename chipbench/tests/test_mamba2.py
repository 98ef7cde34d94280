"""CPU tests of the ``mamba2`` model type and its cell: the program
against the plain reference in float32, the reference's recurrence
against the program's chunked scan, the control and planted faults
failing the cell's limits, and the cell's FLOP and kernel counts.

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests/test_mamba2.py
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.reference import dense_lm, ssm_lm  # noqa: E402

CELL = "mamba2-780m.steady"
CONFIG = os.path.join(ROOT, "chipbench", "configs", "mamba2-780m-1chip.json")
# d_model 64 (128 inner = 8 heads of 16), state 16, 2 layers, 500 ids
# padded to 512 rows, 64 tokens in chunks of 16
TINY = dict(d_model=64, n_layer=2, vocab_size=500)
TINY_LAYER = dict(headdim=16, d_state=16, chunk_size=16)


def _tiny(cfg: dict, **over) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY, **over)
    cfg["mamba2_layer"].update(TINY_LAYER)
    cfg["job"].update(seq_len=64, kernel="jnp")
    return cfg


def _mamba2():
    return harness.load_module("models", "mamba2")


# ---------------------------------------------------------------------------
# the program is the reference, in float32
# ---------------------------------------------------------------------------


def test_reference_is_the_program_in_float32():
    """The set-up's three steps through the program (``build_job`` and
    ``run``) and the plain reference agree to rounding in float32."""
    from chipbench.kinds import train
    cfg = _tiny(harness.load_json(CONFIG), param_dtype="float32")
    traffic = harness.load_json(os.path.join(harness.HERE, "traffic",
                                             "steady.json"))
    seed = 2 ** 33 + 29
    job, state = train.build(cfg, traffic, seed)
    state, _, prog = train.warm(job, state, cfg, traffic, seed,
                                harness.Spans())
    train.free(job, state)
    gaps = dense_lm.compare(prog, train.reference(cfg, seed))
    assert max(gaps.values()) < 1e-5, gaps


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_recurrence_is_the_chunked_scan(chunk):
    """The reference's position-by-position recurrence equals the
    program's chunked dual form (``ssm.ssd_chunked``), two groups of
    heads sharing B and C, decays as strong as the published A and dt
    ranges give."""
    from repro.models.ssm import ssd_chunked
    S, H, P, G, N = 64, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    x = jax.random.normal(ks[0], (S, H, P))
    dt = jnp.exp(jax.random.uniform(ks[1], (S, H), minval=np.log(1e-3),
                                    maxval=np.log(0.5)))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    B = jax.random.normal(ks[3], (S, G, N))
    C = jax.random.normal(ks[4], (S, G, N))
    with jax.default_matmul_precision("highest"):
        want = ssm_lm.recurrence(x, dt, A, B, C, block=16)
        got, _ = ssd_chunked(x[None], dt[None], A, B[None], C[None],
                             chunk=chunk)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# correct comes out false: the control and the planted faults
# ---------------------------------------------------------------------------


def _ctx(**over):
    bench = harness.load_bench()
    cell, cfg, traffic = harness.resolve(bench, CELL)
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            CELL + ".json"))
    return harness.Context(
        bench=bench, cell=cell, config=_tiny(cfg, **over), traffic=traffic,
        seed=2 ** 33 + 17, seconds=1.0, trace=False,
        t_start=time.perf_counter(), devices=jax.devices(), limits=limits,
        compiles=harness.CompileCounter(), tracer=harness.Tracer(False, "t"))


def test_control_fails_a_limit():
    """The reference with its products' operands in float8 (one step
    below the configuration's bfloat16), in the program's place, fails
    at least one of the cell's limits."""
    from chipbench.kinds import train
    ctx = _ctx()
    ref = train.reference(ctx.config, ctx.seed)
    ctl = train.reference(ctx.config, ctx.seed, dtype="float8_e4m3fn")
    vals = dense_lm.compare(ctl, ref)
    assert any(vals[k] > ctx.limits[k] for k in ctx.limits), vals


def _plant(monkeypatch, fault):
    import repro.launch.train as lt
    real_step = lt.make_train_step

    if fault == "state_unchanged":
        def make(model, opt, n_micro, **kw):
            step = real_step(model, opt, n_micro, **kw)

            def broken(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return broken
        monkeypatch.setattr(lt, "make_train_step", make)
    elif fault == "half_batch":
        def make(model, opt, n_micro, **kw):
            step = real_step(model, opt, n_micro // 2, **kw)
            return lambda state, batch: step(
                state, jax.tree.map(lambda a: a[:n_micro // 2], batch))
        monkeypatch.setattr(lt, "make_train_step", make)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_fault_makes_correct_false(fault, monkeypatch, capsys):
    """A whole run of the cell at a tiny size: sound, it is correct; with
    the timed path broken underneath, it is not.  In float32: at this
    size the bfloat16 weights' change is noisier than at the cell's
    widths (``change_leaf_gap`` 1.8e-3 to 6.6e-3 over four seeds, against
    1.4e-3 to 2.0e-3 on the chip), while the float32 program is the
    reference to rounding."""
    from chipbench.kinds import train
    _plant(monkeypatch, fault)
    train.run(_ctx(param_dtype="float32"))
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is (fault is None), res["checks"]


# ---------------------------------------------------------------------------
# the configuration, its FLOPs and the kernel's counts
# ---------------------------------------------------------------------------


def test_configuration_keeps_the_published_widths():
    cfg = harness.load_json(CONFIG)
    arch = _mamba2().arch_config(cfg)
    s = arch.ssm
    assert (arch.d_model, s.expand, s.head_dim, s.d_state, s.d_conv,
            s.chunk) == (1536, 2, 64, 128, 4, 256)
    assert s.n_heads(arch.d_model) == 48 and arch.vocab == 50288
    assert arch.norm_eps == 1e-5 and arch.residual_in_fp32
    assert arch.n_layers == 12 and list(cfg["reduced"]) == ["n_layer"]
    # the weights have the layout and types of the program's model
    from repro.models.model import build_model
    like = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: _mamba2().params(cfg, k),
                         jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), like)
    assert arch.param_count() == sum(a.size for a in jax.tree.leaves(got))


@pytest.mark.parametrize("key,value", [
    ("ngroups", 2), ("norm_before_gate", True), ("D_has_hdim", True)])
def test_configuration_the_program_cannot_run_is_refused(key, value):
    """A Mamba2 layer setting the program's block does not implement is
    refused, not run as something else."""
    cfg = harness.load_json(CONFIG)
    cfg["mamba2_layer"][key] = value
    with pytest.raises(ValueError):
        _mamba2().arch_config(cfg)


def test_benchmark_weights_follow_the_published_init():
    cfg = _tiny(harness.load_json(CONFIG))
    cfg["n_layer"] = 6
    p = _mamba2().params(cfg, jax.random.PRNGKey(3))["segments"][0][0]
    A = -np.exp(np.asarray(p["mamba"]["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["mamba"]["dt_bias"]))
    assert A.shape == dt.shape == (6, 8)
    assert np.all((A >= -16) & (A <= -1)) and np.ptp(A) > 1
    assert np.all((dt >= 1e-3 * (1 - 1e-5)) & (dt <= 0.1 * (1 + 1e-5)))
    np.testing.assert_array_equal(np.asarray(p["mamba"]["D"]), 1.0)


def test_train_flops_of_the_cell():
    cfg = harness.load_json(CONFIG)
    m = _mamba2()
    proj = 1536 * (2 * 3072 + 2 * 128 + 48) + 3072 * 1536
    assert m.matmul_params(cfg) == 12 * proj + 50288 * 1536
    # per token and layer: lower triangles of C B^T (one group) and of
    # the scores times x (48 heads of 64), chunk states and carried state
    ssd = 2 * (128.5 * 128 + 128.5 * 48 * 64 + 2 * 48 * 64 * 128)
    assert m.ssd_token_flops(cfg) == pytest.approx(ssd)
    per_token = 3 * (2 * m.matmul_params(cfg) + 12 * ssd)
    assert m.train_flops(cfg, 4096, 4) == pytest.approx(per_token * 16384)
    assert 1.59e9 < per_token < 1.61e9


def test_ssd_kernel_counts_and_operands():
    cfg = harness.load_json(CONFIG)
    m = _mamba2()
    ops, nbytes = m.ssd_fwd(cfg, 1, 4096)
    assert ops == pytest.approx(4096 * m.ssd_token_flops(cfg))
    elems = 4096 * (2 * 48 * 64 + 48 + 2 * 128) + 48 + 48 * 64 * 128
    assert nbytes == 4 * elems
    assert m.ssd_operands(cfg, 1, 4096) == ("f32[1,48,4096,64]",
                                            "f32[1,1,4096,128]")
    # the operands the program hands the kernel at the cell's sizes
    from repro.kernels import ssd_scan
    seen = []

    def spy(*args, **kw):
        seen.append([a.shape for a in args])
        return (jnp.zeros((1, 48, 4096, 64), jnp.float32),
                jnp.zeros((1, 48, 64, 128), jnp.float32))

    real = ssd_scan.pl.pallas_call
    try:
        ssd_scan.pl.pallas_call = lambda *a, **kw: spy
        jax.eval_shape(lambda *a: ssd_scan.ssd_scan_fwd(*a, chunk=256),
                       jax.ShapeDtypeStruct((1, 4096, 48, 64), jnp.float32),
                       jax.ShapeDtypeStruct((1, 4096, 48), jnp.float32),
                       jax.ShapeDtypeStruct((48,), jnp.float32),
                       jax.ShapeDtypeStruct((1, 4096, 1, 128), jnp.float32),
                       jax.ShapeDtypeStruct((1, 4096, 1, 128), jnp.float32))
    finally:
        ssd_scan.pl.pallas_call = real
    x, _, _, b, c = seen[0]
    assert tuple(x) == (1, 48, 4096, 64) and tuple(b) == tuple(c) == (
        1, 1, 4096, 128)
