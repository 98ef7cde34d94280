"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Each case compiles one kernel at real widths for a described (not
attached) v5e chip and asserts that the compiled program holds the Mosaic
kernel (``tpu_custom_call``): what interpret-mode tests cannot show, such
as block shapes that break the (8, 128) tiling, fails here.  Nothing runs.
The topology is described inside a fixture, so importing this file loads
no TPU library; where it cannot be described, every case skips.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.maxplus import maxplus_conv_batched, maxplus_scan_chunk
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_scan_bwd, ssd_scan_fwd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qwen3_4b_attention():
    """Flash attention forward at qwen3-4b widths, 4096 tokens."""
    a = get_arch("qwen3-4b").attn
    q = ((1, 4096, a.n_heads, a.head_dim), jnp.bfloat16)
    kv = ((1, 4096, a.n_kv_heads, a.head_dim), jnp.bfloat16)
    return partial(flash_attention_fwd, interpret=False), [q, kv, kv]


def _qwen3_4b_rmsnorm():
    """RMSNorm over 8192 tokens of qwen3-4b's d_model."""
    d = get_arch("qwen3-4b").d_model
    return (partial(rmsnorm_fwd, interpret=False),
            [((8192, d), jnp.bfloat16), ((d,), jnp.bfloat16)])


def _mamba2_780m_ssd():
    """SSD chunk scan at mamba2-780m widths (48 heads x 64, state 128)."""
    cfg = get_arch("mamba2-780m")
    s = cfg.ssm
    h, seq = s.n_heads(cfg.d_model), 4096
    return (partial(ssd_scan_fwd, chunk=s.chunk, interpret=False),
            [((1, seq, h, s.head_dim), jnp.float32),
             ((1, seq, h), jnp.float32), ((h,), jnp.float32),
             ((1, seq, 1, s.d_state), jnp.float32),
             ((1, seq, 1, s.d_state), jnp.float32)])


def _ssd_bwd(arch: str, seq: int = 4096):
    """SSD backward kernel at an architecture's widths: the forward's
    operands, then the cotangents of y and of the final state."""
    cfg = get_arch(arch)
    s = cfg.ssm
    h, p, n = s.n_heads(cfg.d_model), s.head_dim, s.d_state
    return (partial(ssd_scan_bwd, chunk=s.chunk, interpret=False),
            [((1, seq, h, p), jnp.float32), ((1, seq, h), jnp.float32),
             ((h,), jnp.float32), ((1, seq, 1, n), jnp.float32),
             ((1, seq, 1, n), jnp.float32), ((1, seq, h, p), jnp.float32),
             ((1, h, p, n), jnp.float32)])


# the fused planner's inner step at the headline fleet (n=1024, m=32):
# 32 stacked windows of n+1 + K-1 cells, chunk K=16
def _planner_scan_chunk():
    """maxplus_scan_chunk at the fused planner's (n=1024, m=32) shapes."""
    return (partial(maxplus_scan_chunk, interpret=False),
            [((32, 1041), jnp.float32), ((32, 16), jnp.float32)])


def _planner_conv_batched():
    """maxplus_conv_batched over the (m=32, n+1=1025) dense reward stack."""
    return (partial(maxplus_conv_batched, interpret=False),
            [((32, 1025), jnp.float32), ((32, 1025), jnp.float32)])


CASES = {
    "flash_attention_qwen3_4b": _qwen3_4b_attention,
    "rmsnorm_qwen3_4b": _qwen3_4b_rmsnorm,
    "ssd_scan_mamba2_780m": _mamba2_780m_ssd,
    "ssd_scan_bwd_mamba2_780m": partial(_ssd_bwd, "mamba2-780m"),
    "ssd_scan_bwd_zamba2_1p2b": partial(_ssd_bwd, "zamba2-1.2b"),
    "maxplus_scan_chunk_n1024_m32": _planner_scan_chunk,
    "maxplus_conv_batched_n1024_m32": _planner_conv_batched,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_backward_operands_are_chunk_blocked(one_chip):
    """The backward kernel takes x and B/C in the chunk-blocked layout
    (B, H or G, chunks, L, .), never the forward's head-major
    (B, H or G, S, .) shapes, so that a trace tells the two kernels
    apart by their operands' shapes."""
    cfg = get_arch("mamba2-780m")
    s = cfg.ssm
    h, seq = s.n_heads(cfg.d_model), 4096
    head_major = (f"f32[1,{h},{seq},{s.head_dim}]",
                  f"f32[1,1,{seq},{s.d_state}]")

    def kernel_line(case):
        fn, shapes = case()
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        (line,) = [ln for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        return line

    assert all(sh in kernel_line(_mamba2_780m_ssd) for sh in head_major)
    bwd = kernel_line(partial(_ssd_bwd, "mamba2-780m"))
    assert not any(sh in bwd for sh in head_major)
