"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # B, Sq, Sk, H, KV, D, causal, window, softcap
    (2, 128, 128, 4, 2, 64, True, 0, 0.0),      # GQA causal
    (1, 100, 100, 4, 1, 32, True, 0, 0.0),      # MQA, ragged seq
    (2, 64, 64, 8, 8, 16, True, 16, 0.0),       # sliding window
    (1, 256, 256, 2, 2, 64, False, 0, 0.0),     # bidirectional (hubert)
    (1, 96, 96, 4, 2, 64, True, 0, 30.0),       # logit softcap (gemma)
    (1, 64, 192, 2, 2, 32, True, 0, 0.0),       # cross-length (q_offset)
]


@pytest.mark.parametrize(
    "B,Sq,Sk,H,KV,D,causal,window,softcap", ATTN_CASES)
def test_flash_attention_matches_oracle(B, Sq, Sk, H, KV, D, causal,
                                        window, softcap):
    ks = jax.random.split(jax.random.fold_in(KEY, Sq * Sk + H), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, KV, D))
    v = jax.random.normal(ks[2], (B, Sk, KV, D))
    off = Sk - Sq
    got = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=off,
                              block_q=32, block_k=32)
    want = ref.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=off)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 64, 2, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 64, 2, 32)).astype(dtype)
    got = flash_attention_fwd(q, k, v, block_q=32, block_k=32)
    want = ref.flash_attention(q, k, v)
    assert got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(16, 16), (32, 64), (128, 128)])
def test_flash_attention_block_shape_invariance(block_q, block_k):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 80, 2, 32))
    k = jax.random.normal(ks[1], (1, 80, 2, 32))
    v = jax.random.normal(ks[2], (1, 80, 2, 32))
    got = flash_attention_fwd(q, k, v, block_q=block_q, block_k=block_k)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_grad_matches_oracle_grad():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 48, 2, 16))
    k = jax.random.normal(ks[1], (1, 48, 2, 16))
    v = jax.random.normal(ks[2], (1, 48, 2, 16))
    g1 = jax.grad(lambda q: jnp.sum(ops.flash_attention(q, k, v) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(ref.flash_attention(q, k, v) ** 2))(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # B, S, H, P, G, N, chunk
    (2, 64, 4, 16, 1, 8, 16),
    (1, 100, 2, 32, 1, 16, 32),      # ragged
    (1, 128, 4, 8, 2, 8, 128),       # multi-group, single chunk
    (2, 37, 2, 8, 1, 4, 16),         # S < 2 chunks, ragged
]
SSD_GRAD_CASES = SSD_CASES + [
    (1, 512, 2, 64, 1, 128, 256),    # mamba2-780m's chunk and state
]


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", SSD_CASES)
def test_ssd_scan_matches_oracle(B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + P), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    y, fin = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=chunk)
    yr, finr = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(fin, finr, atol=1e-4, rtol=1e-4)


def _no_oracle(monkeypatch):
    """Make any use of the jnp oracle raise: the kernel's custom VJP must
    not fall back to differentiating it."""
    from repro.models import ssm

    def oracle(*a, **kw):
        raise AssertionError("ssd_scan's backward used the jnp oracle")
    monkeypatch.setattr(ref, "ssd_scan", oracle)
    monkeypatch.setattr(ref, "ssd_chunked", oracle)
    monkeypatch.setattr(ssm, "ssd_chunked", oracle)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", SSD_GRAD_CASES)
def test_ssd_scan_grad_matches_oracle(B, S, H, P, G, N, chunk, monkeypatch):
    """The Pallas backward's cotangents of all five inputs against
    ``jax.vjp`` of the oracle, with random cotangents on y and on the
    final state."""
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + P), 7)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    g = (jax.random.normal(ks[5], (B, S, H, P)),
         jax.random.normal(ks[6], (B, H, P, N)))
    args = (x, dt, A, Bm, Cm)
    _, vjp = jax.vjp(lambda *a: ref.ssd_scan(*a, chunk=chunk), *args)
    want = vjp(g)
    _no_oracle(monkeypatch)
    _, vjp = jax.vjp(lambda *a: ops.ssd_scan(*a, chunk), *args)
    got = vjp(g)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, rtol=1e-4,
                                   err_msg=name)


def test_ssd_scan_grad_is_finite_under_strong_decay(monkeypatch):
    """The published A (down to -16) at dt 0.1 decays by e^-1.6 a token:
    over a 256-token chunk the exponent above the diagonal overflows and
    exp(acum) underflows, and the kernel's gradient stays finite and
    equal to the oracle's."""
    S, H, P, N, chunk = 512, 2, 8, 16, 256
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (1, S, H, P))
    Bm = jax.random.normal(ks[1], (1, S, 1, N))
    Cm = jax.random.normal(ks[2], (1, S, 1, N))
    args = (jnp.full((1, S, H), 0.1), jnp.array([-16.0, -1.0]))

    def loss(scan):
        return lambda dt, A: sum(
            o.sum() for o in scan(x, dt, A, Bm, Cm))

    want = jax.grad(loss(lambda *a: ref.ssd_scan(*a, chunk=chunk)),
                    argnums=(0, 1))(*args)
    _no_oracle(monkeypatch)
    got = jax.grad(loss(lambda *a: ops.ssd_scan(*a, chunk)),
                   argnums=(0, 1))(*args)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_ssd_scan_matches_serial_recurrence():
    """Second-level oracle: token-serial SSM recurrence."""
    from repro.models.ssm import ssd_decode_step
    B, S, H, P, N = 1, 24, 2, 4, 4
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, 1, N))
    Cm = jax.random.normal(ks[4], (B, S, 1, N))
    y, fin = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=8)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        yt, state = ssd_decode_step(state, x[:, t], dt[:, t], A,
                                    Bm[:, t], Cm[:, t])
        ys.append(yt)
    y_serial = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y, y_serial, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(fin, state, atol=1e-4, rtol=1e-4)


def test_ssd_scan_chunk_invariance():
    B, S, H, P, N = 1, 96, 2, 8, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, 1, N))
    Cm = jax.random.normal(ks[4], (B, S, 1, N))
    y16, _ = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=16)
    y48, _ = ssd_scan_fwd(x, dt, A, Bm, Cm, chunk=48)
    np.testing.assert_allclose(y16, y48, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused RMSNorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 32), (2, 17, 96), (1, 5, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], shape).astype(dtype)
    s = jax.random.normal(ks[1], (shape[-1],)).astype(dtype)
    got = rmsnorm_fwd(x, s, block_rows=8)
    want = ref.rmsnorm(x, s)
    assert got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2
                               if dtype == jnp.bfloat16 else 1e-5)


def test_rmsnorm_grad():
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (6, 32))
    s = jax.random.normal(ks[1], (32,))
    g1 = jax.grad(lambda x, s: jnp.sum(ops.rmsnorm(x, s) ** 2), (0, 1))(x, s)
    g2 = jax.grad(lambda x, s: jnp.sum(ref.rmsnorm(x, s) ** 2), (0, 1))(x, s)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# max-plus convolution (planner DP kernel)
# ---------------------------------------------------------------------------


def _maxplus_case(seed, monotone=False, cap=None):
    rng = np.random.RandomState(seed)
    n = rng.randint(0, 200)
    prev = rng.uniform(-50.0, 50.0, n + 1)
    if monotone:
        prev = np.maximum.accumulate(prev)
    g = rng.uniform(-50.0, 50.0, n + 1)
    band = None
    if cap is not None:
        band = min(cap, n)
        g[band:] = g[band]
    return prev, g, band


@pytest.mark.parametrize("seed", range(8))
def test_maxplus_dense_matches_numpy_oracle(seed):
    """Pallas maxplus (interpret off-TPU) == the f32 numpy oracle with the
    kernel's candidate arithmetic, dense band."""
    from repro.kernels.maxplus import maxplus_conv, maxplus_conv_np
    prev, g, _ = _maxplus_case(seed)
    got = np.asarray(maxplus_conv(prev, g))
    want = maxplus_conv_np(prev, g)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("seed,cap", [(0, 0), (1, 1), (2, 7), (3, 32),
                                      (4, 100)])
def test_maxplus_banded_matches_dense(seed, cap):
    """Under the band contract (monotone prev, g flat past the band) the
    banded kernel equals the dense convolution."""
    from repro.kernels.maxplus import maxplus_conv, maxplus_conv_np
    prev, g, band = _maxplus_case(seed, monotone=True, cap=cap)
    got = np.asarray(maxplus_conv(prev, g, band=band))
    dense = maxplus_conv_np(prev, g)           # f32 oracle, full band
    np.testing.assert_allclose(got, dense, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("seed", range(6))
def test_maxplus_batched_matches_2d_kernel(seed):
    """The grid-batched Pallas kernel equals per-slice 2-D ``maxplus_conv``
    calls (and the f32 numpy oracle) on stacks with mixed per-row bands —
    the equivalence CI pins under REPRO_PALLAS_INTERPRET=1."""
    from repro.kernels.maxplus import (maxplus_conv, maxplus_conv_batched,
                                       maxplus_conv_np)
    rng = np.random.RandomState(seed)
    B = rng.randint(1, 5)
    n = rng.randint(0, 120)
    prev = np.maximum.accumulate(
        rng.uniform(-50.0, 50.0, (B, n + 1)).astype(np.float32), axis=1)
    g = rng.uniform(-50.0, 50.0, (B, n + 1)).astype(np.float32)
    bands = []
    for r in range(B):
        band = rng.choice([None, rng.randint(0, n + 1)])
        if band is not None:
            band = int(band)
            g[r, band:] = g[r, min(band, n)]
        bands.append(band)
    got = np.asarray(maxplus_conv_batched(prev, g, bands))
    assert got.shape == (B, n + 1)
    for r in range(B):
        want = np.asarray(maxplus_conv(prev[r], g[r], band=bands[r]))
        np.testing.assert_allclose(got[r], want, rtol=1e-6, atol=1e-5)
        oracle = maxplus_conv_np(prev[r], g[r], band=bands[r])
        np.testing.assert_allclose(got[r], oracle, rtol=1e-6, atol=1e-5)


def test_maxplus_batched_scalar_band_and_shape_checks():
    """Scalar band broadcast, band-count validation and 1-D rejection."""
    from repro.kernels.maxplus import maxplus_conv, maxplus_conv_batched
    rng = np.random.RandomState(11)
    prev = np.maximum.accumulate(
        rng.uniform(0, 10, (3, 33)).astype(np.float32), axis=1)
    g = rng.uniform(0, 10, (3, 33)).astype(np.float32)
    g[:, 8:] = g[:, 8:9]
    got = np.asarray(maxplus_conv_batched(prev, g, 8))
    for r in range(3):
        want = np.asarray(maxplus_conv(prev[r], g[r], band=8))
        np.testing.assert_allclose(got[r], want, rtol=1e-6, atol=1e-5)
    with pytest.raises(ValueError):
        maxplus_conv_batched(prev[0], g[0])
    with pytest.raises(ValueError):
        maxplus_conv_batched(prev, g, [8, 8])


def test_maxplus_matches_planner_float64_kernel():
    """The float32 kernel tracks the planner's float64 value kernel to f32
    precision on O(100) data — the interpret-mode equivalence the CI step
    pins (``_maxplus_vals`` is the PR-1 ground-truth kernel)."""
    from repro.core.planner import _maxplus_vals
    from repro.kernels.maxplus import maxplus_conv
    for seed in range(6):
        prev, g, _ = _maxplus_case(seed, monotone=True)
        got = np.asarray(maxplus_conv(prev, g))
        want = _maxplus_vals(prev, g)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_maxplus_planner_backend_end_to_end():
    """A PlanTable built with REPRO_PLANNER_BACKEND=pallas (via the
    setter) matches the all-scalar reference table to f32 tolerance."""
    from repro.configs import get_arch
    from repro.core.costmodel import A800, TaskModel
    from repro.core.planner import (PlanTable, set_maxplus_backend,
                                    solve_reference)
    from repro.core.waf import Task
    tasks = [Task(model=TaskModel.from_arch(get_arch("gpt3-1.3b"),
                                            global_batch=256),
                  weight=1.0, max_workers=8),
             Task(model=TaskModel.from_arch(get_arch("gpt3-7b"),
                                            global_batch=256),
                  weight=1.3)]
    ref = PlanTable(tasks, [8, 16], A800, 3600.0, 120.0,
                    incremental=False, solver=solve_reference)
    set_maxplus_backend("pallas")
    try:
        seg = PlanTable(tasks, [8, 16], A800, 3600.0, 120.0)
    finally:
        set_maxplus_backend(None)
    assert set(seg.table) == set(ref.table)
    for key in ref.table:
        a, b = seg.table[key], ref.table[key]
        rel = abs(a.total_reward - b.total_reward) / max(
            1.0, abs(b.total_reward))
        assert rel < 1e-5, (key, rel)


@pytest.mark.parametrize("seed", range(6))
def test_maxplus_scan_chunk_matches_oracle(seed):
    """The scan-compatible chunk kernel (the fused planner engine's inner
    step) against its numpy oracle:
    ``out[r, j] = max_k wins[r, j + K - 1 - k] + gs[r, k]``."""
    from repro.kernels.maxplus import NEG, maxplus_scan_chunk
    rng = np.random.RandomState(seed)
    B = rng.randint(1, 6)
    K = rng.randint(1, 33)
    n1 = rng.randint(1, 200)
    wins = rng.uniform(-50.0, 50.0, (B, n1 + K - 1)).astype(np.float32)
    gs = rng.uniform(-50.0, 50.0, (B, K)).astype(np.float32)
    # -inf masking (how the fused program disables off-band candidates
    # and dummy rows) must stay a no-op candidate, not a NaN source
    gs[rng.uniform(size=gs.shape) < 0.2] = NEG
    got = np.asarray(maxplus_scan_chunk(wins, gs))
    assert got.shape == (B, n1)
    want = np.full((B, n1), NEG, dtype=np.float32)
    for k in range(K):
        want = np.maximum(want, wins[:, K - 1 - k:K - 1 - k + n1]
                          + gs[:, k:k + 1])
    np.testing.assert_array_equal(got, want)


def test_maxplus_f32_error_budget_paper_scale():
    """f32 error budget for the Pallas kernels on PAPER-SCALE reward
    rows — real cost-model reward curves (O(1e2..1e4) values, O(1e-3)
    increments), chained through an m-task DP exactly as the planner
    composes them — against the f64 numpy kernel (``_maxplus_vals``).

    Documented budget: **1e-6 relative** on every DP cell, per
    convolution AND accumulated over the full chain.  Observed error
    (f32 input rounding, one add per candidate, order-free max) is
    ~2e-7 chained and ~6e-7 on the raw-row stack, so the budget binds —
    any extra f32 rounding stage in the kernels would trip it.  This is
    the gate the ROADMAP requires before the pallas backend can ever
    become the default."""
    from repro.configs import get_arch
    from repro.core.costmodel import A800, TaskModel
    from repro.core.planner import PlanTable, _maxplus_vals
    from repro.core.waf import Task
    from repro.kernels.maxplus import maxplus_conv, maxplus_conv_batched
    tasks = [Task(model=TaskModel.from_arch(get_arch(size),
                                            global_batch=256),
                  weight=w, max_workers=64)
             for size, w in (("gpt3-1.3b", 1.0), ("gpt3-7b", 1.3),
                             ("gpt3-13b", 0.7), ("gpt3-1.3b", 2.0))]
    table = PlanTable(tasks, [32] * len(tasks), A800, 3600.0, 120.0,
                      lazy=True, n_budget=512)
    rows = [np.asarray(table._row(i), dtype=np.float64)
            for i in range(len(tasks))]

    def rel(a, b):
        return np.max(np.abs(np.asarray(a, dtype=np.float64) - b)
                      / np.maximum(np.abs(b), 1.0))

    # chained DP: f32 kernel output feeds the next f32 convolution, so
    # rounding accumulates exactly as it would in a pallas-backed build
    # (leaf = running max over budgets, like the engines' DP leaves)
    prev64 = np.maximum.accumulate(rows[0])
    prev32 = prev64.astype(np.float32)
    worst = 0.0
    for g in rows[1:]:
        prev64 = _maxplus_vals(prev64, g)
        prev32 = np.asarray(maxplus_conv(prev32, g.astype(np.float32)))
        worst = max(worst, rel(prev32, prev64))
    assert worst < 1e-6, f"chained f32 DP error {worst:.2e} over budget"

    # grid-batched kernel on the raw reward stack, same budget
    stack32 = np.stack(rows).astype(np.float32)
    prev_stack = np.stack([np.maximum.accumulate(r) for r in rows])
    got = np.asarray(maxplus_conv_batched(
        prev_stack.astype(np.float32), stack32))
    worst_b = max(rel(got[r], _maxplus_vals(prev_stack[r], rows[r]))
                  for r in range(len(rows)))
    assert worst_b < 1e-6, f"batched f32 error {worst_b:.2e} over budget"
    print(f"[f32 budget] chained {worst:.2e}, batched {worst_b:.2e} "
          f"(budget 1e-6)")


# ---------------------------------------------------------------------------
# end-to-end kernel path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-4b", "mamba2-780m", "gemma3-12b"])
def test_pallas_path_matches_jnp_path(arch):
    from repro.configs import get_arch
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import build_model
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticLM(cfg, seq_len=64, global_batch=2).batch(0)
    l1, _ = model.loss(params, batch, kernel="jnp")
    l2, _ = model.loss(params, batch, kernel="pallas")
    assert abs(float(l1) - float(l2)) < 1e-4


# ---------------------------------------------------------------------------
# flash custom-VJP blocked attention (perf variant "flash")
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,H,KV,D,causal,window,softcap",
    [(2, 128, 4, 2, 64, True, 0, 0.0),
     (1, 100, 4, 1, 32, True, 0, 0.0),
     (2, 64, 8, 8, 16, True, 16, 0.0),
     (1, 96, 4, 2, 64, True, 0, 30.0)])
def test_flash_vjp_matches_oracle(B, S, H, KV, D, causal, window, softcap):
    from repro.models.flash_vjp import flash_attention_jnp
    ks = jax.random.split(jax.random.fold_in(KEY, S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))

    def f(q, k, v):
        return flash_attention_jnp(q, k, v, causal, window, softcap, 0,
                                   32, 32)

    def r(q, k, v):
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, q_offset=0)

    np.testing.assert_allclose(f(q, k, v), r(q, k, v), atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2), (0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(r(q, k, v) ** 2), (0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_kernel_path_end_to_end():
    from repro.configs import get_arch
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import build_model
    for arch in ("qwen3-4b", "deepseek-v3-671b"):
        cfg = get_arch(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = SyntheticLM(cfg, seq_len=64, global_batch=2).batch(0)
        l1, _ = model.loss(params, batch, kernel="jnp")
        l2, _ = model.loss(params, batch, kernel="flash")
        assert abs(float(l1) - float(l2)) < 1e-4, arch


def test_moe_shardmap_matches_reference():
    from repro.configs import get_arch
    from repro.data.pipeline import SyntheticLM
    from repro.models import moe
    from repro.models.model import build_model
    cfg = get_arch("granite-moe-3b-a800m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticLM(cfg, seq_len=32, global_batch=2).batch(0)
    l_ref, _ = model.loss(params, batch)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    moe.SHARD_MAP = (mesh, ("data",))
    try:
        l_sm, _ = model.loss(params, batch)
        g = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    finally:
        moe.SHARD_MAP = None
    assert abs(float(l_ref) - float(l_sm)) < 1e-5
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


def test_moe_dispatch_3d_matches_flat():
    from repro.configs import get_arch
    from repro.data.pipeline import SyntheticLM
    from repro.models import moe
    from repro.models.model import build_model
    cfg = get_arch("granite-moe-3b-a800m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = SyntheticLM(cfg, seq_len=32, global_batch=2).batch(0)
    l_flat, _ = model.loss(params, batch)
    moe.DISPATCH_3D = True
    try:
        l_3d, _ = model.loss(params, batch)
    finally:
        moe.DISPATCH_3D = False
    assert abs(float(l_flat) - float(l_3d)) < 1e-6
