"""The Mamba2 block and the settings it reads from its configuration: the
norms' epsilon, the float32 residual stream, the published initialisation
of A, dt and D, and the parameter count against what ``init`` allocates."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import blocks, ssm
from repro.models.model import build_model


def _tiny_mamba(**over):
    cfg = dataclasses.replace(get_arch("mamba2-780m").reduced(),
                              param_dtype="bfloat16")
    return dataclasses.replace(cfg, **over)


@pytest.mark.parametrize("eps", [1e-5, 0.5])
def test_norm_eps_follows_the_config(eps):
    """Every RMSNorm of a block, the gated one included, uses
    ``cfg.norm_eps``."""
    cfg = _tiny_mamba(norm_eps=eps, param_dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, cfg.d_model)) * 0.3
    scale = jnp.full((cfg.d_model,), 1.5)

    def rms(v):
        return v / np.sqrt(np.mean(np.square(v), -1, keepdims=True) + eps)

    got = blocks.pre_norm({"scale": scale}, cfg, x)
    np.testing.assert_allclose(got, 1.5 * rms(np.asarray(x)), rtol=1e-5)
    di = cfg.ssm.d_inner(cfg.d_model)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 8, di)) * 0.3
    z = jax.random.normal(jax.random.PRNGKey(2), (2, 8, di))
    g = ssm._gate({"gate_norm": jnp.ones((di,))}, cfg, y, z)
    want = rms(np.asarray(y * jax.nn.silu(z)))
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)


def test_gated_norm_runs_in_float32():
    """The gate multiplies the scan's float32 output by silu(z) and
    normalises in float32, as published; a product rounded to the
    weights' bfloat16 first would be off by ~2**-9."""
    cfg = _tiny_mamba()
    di = cfg.ssm.d_inner(cfg.d_model)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 8, di))
    z = jax.random.normal(jax.random.PRNGKey(2), (2, 8, di), jnp.bfloat16)
    g = ssm._gate({"gate_norm": jnp.ones((di,), jnp.bfloat16)}, cfg, y, z)
    assert g.dtype == jnp.float32
    v = np.asarray(y) * np.asarray(jax.nn.silu(z.astype(jnp.float32)))
    want = v / np.sqrt(np.mean(np.square(v), -1, keepdims=True)
                       + cfg.norm_eps)
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fp32", [True, False])
def test_residual_stream_type_follows_the_config(fp32, monkeypatch):
    """With ``residual_in_fp32`` the stream between blocks is float32 and
    each mixer still sees the weights' type; without it both are
    bfloat16."""
    cfg = _tiny_mamba(residual_in_fp32=fp32)
    model = build_model(cfg)
    seen = {"stream": set(), "mixer": set()}
    real_block, real_mamba = blocks.block_apply, ssm.mamba_apply

    def block_apply(p, cfg, kind, x, *a, **kw):
        seen["stream"].add(x.dtype)
        return real_block(p, cfg, kind, x, *a, **kw)

    def mamba_apply(p, cfg, x, **kw):
        seen["mixer"].add(x.dtype)
        return real_mamba(p, cfg, x, **kw)

    monkeypatch.setattr(blocks, "block_apply", block_apply)
    monkeypatch.setattr(ssm, "mamba_apply", mamba_apply)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    loss = jax.eval_shape(lambda p, t: model.loss(p, {"tokens": t})[0],
                          params, toks)
    assert loss.dtype == jnp.float32
    want = jnp.float32 if fp32 else jnp.bfloat16
    assert seen == {"stream": {jnp.dtype(want)},
                    "mixer": {jnp.dtype(jnp.bfloat16)}}


def test_init_mamba_follows_the_published_ranges():
    """A = -exp(A_log) uniform on [-16, -1], dt = softplus(dt_bias)
    log-uniform on [1e-3, 1e-1], D one, each head its own draw."""
    cfg = get_arch("mamba2-780m")
    p = ssm.init_mamba(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    H = cfg.ssm.n_heads(cfg.d_model)
    A = -np.exp(np.asarray(p["A_log"], np.float64))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]), np.float64)
    assert A.shape == dt.shape == (H,) == (48,)
    assert np.all((A >= -16) & (A <= -1)) and np.ptp(A) > 5
    assert np.all((dt >= 1e-3 * (1 - 1e-5)) & (dt <= 0.1 * (1 + 1e-5)))
    assert np.ptp(np.log(dt)) > 2                  # spread over decades
    np.testing.assert_array_equal(np.asarray(p["D"]), 1.0)
    assert p["dt_bias"].dtype == p["A_log"].dtype == jnp.float32


@pytest.mark.parametrize("arch", ["mamba2-780m", "qwen3-4b"])
def test_param_count_is_what_init_allocates(arch):
    cfg = get_arch(arch)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert cfg.param_count() == sum(a.size for a in jax.tree.leaves(shapes))


@pytest.mark.parametrize("chunk", [128, 256])
def test_chunked_scan_gradient_is_finite_under_strong_decay(chunk):
    """Above a chunk's diagonal the exponent is a growing sum of decays;
    with the published A (down to -16) and dt its exp overflows, and the
    masked entries must not turn the gradient NaN."""
    S, H, P, N = 512, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (1, S, H, P))
    B = jax.random.normal(ks[1], (1, S, 1, N))
    C = jax.random.normal(ks[2], (1, S, 1, N))
    A = jnp.array([-16.0, -1.0])

    def loss(dt, A):
        return ssm.ssd_chunked(x, dt, A, B, C, chunk=chunk)[0].sum()

    g_dt, g_A = jax.grad(loss, argnums=(0, 1))(jnp.full((1, S, H), 0.1), A)
    assert np.isfinite(np.asarray(g_dt)).all()
    assert np.isfinite(np.asarray(g_A)).all()


def test_mixer_grad_pallas_matches_jnp():
    """The block's gradients through the mixer's checkpoint, which keeps
    only the scan's output for the backward pass, agree between the
    Pallas scan (its backward kernel fed the recomputed inputs) and the
    autodiffed jnp scan: a residual that the policy dropped or
    recomputed wrongly would part them."""
    cfg = _tiny_mamba(param_dtype="float32")
    p = ssm.init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    S = 3 * cfg.ssm.chunk + 5                          # ragged last chunk
    x = jax.random.normal(ks[0], (2, S, cfg.d_model))
    ct = jax.random.normal(ks[1], (2, S, cfg.d_model))

    def grads(kernel):
        def f(p, x):
            return jnp.vdot(ssm.mamba_apply(p, cfg, x, kernel=kernel), ct)
        return jax.grad(f, argnums=(0, 1))(p, x)

    want, got = grads("jnp"), grads("pallas")
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, b), a in zip(flat_want, jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))
