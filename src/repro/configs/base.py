"""Configuration system for the Unicron reproduction framework.

Every architecture assigned to this paper is expressed as an
:class:`ArchConfig`.  Configs are plain frozen dataclasses so they can be
hashed, used as jit static args, and copied into reduced "smoke" variants
(``reduced()``) that run one forward/train step on CPU.

The four canonical input shapes (train_4k / prefill_32k / decode_32k /
long_500k) are :class:`ShapeConfig` instances in ``SHAPES``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings (GShard/DeepSeek style)."""

    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0          # DeepSeek shared experts
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01    # load-balance loss weight
    router_dtype: str = "float32"


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) settings."""

    d_state: int
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 128                   # SSD chunk length

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 Multi-head Latent Attention settings."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class AttnConfig:
    """Plain / GQA / MQA attention settings."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False              # qwen3-style per-head RMSNorm on q,k
    causal: bool = True                # False for encoder-only (hubert)
    # Sliding-window pattern: window > 0 means local attention with the
    # given window; ``local_ratio`` of (local, global) layers per period,
    # e.g. gemma3 uses (5, 1) -> 5 local layers then 1 global layer.
    window: int = 0
    local_ratio: Tuple[int, int] = (0, 1)
    rope_theta: float = 10000.0
    logit_softcap: float = 0.0


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------

# block kinds used by the model builder
BLOCK_ATTN_DENSE = "attn_dense"        # attention + dense MLP
BLOCK_ATTN_MOE = "attn_moe"            # attention + MoE FFN
BLOCK_MLA_DENSE = "mla_dense"          # MLA attention + dense MLP
BLOCK_MLA_MOE = "mla_moe"              # MLA attention + MoE FFN
BLOCK_MAMBA = "mamba"                  # Mamba2 SSD block
BLOCK_HYBRID_SHARED = "hybrid_shared"  # zamba2: mamba layers + shared attn


@dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str                     # dense | moe | ssm | hybrid | vlm | audio
    source: str                        # citation for the config numbers
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    attn: Optional[AttnConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None

    # dense-layer prefix before MoE layers (deepseek: first 3 dense)
    n_dense_prefix: int = 0
    # zamba2: shared attention block applied every `shared_period` layers
    shared_period: int = 0

    mlp_act: str = "silu"              # silu (SwiGLU) | gelu (GeGLU)
    gated_mlp: bool = True             # False = classic 2-matrix MLP (GPT-3)
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    tie_embeddings: bool = False
    encoder_only: bool = False         # hubert: no decode step
    modality: str = "text"             # text | vision_stub | audio_stub
    n_prefix_embeds: int = 0           # VLM patch / audio frame positions
    mtp: bool = False                  # DeepSeek multi-token-prediction head
    embed_scale: bool = False          # gemma: scale embeddings by sqrt(d)
    param_dtype: str = "bfloat16"
    norm_eps: float = 1e-6             # every RMSNorm / LayerNorm epsilon
    residual_in_fp32: bool = False     # mamba: float32 residual stream

    # ---- derived helpers ---------------------------------------------------

    @property
    def block_pattern(self) -> Tuple[Tuple[str, int], ...]:
        """Sequence of (block_kind, count) segments for the layer stack."""
        if self.arch_type == "ssm":
            return ((BLOCK_MAMBA, self.n_layers),)
        if self.arch_type == "hybrid":
            return ((BLOCK_HYBRID_SHARED, self.n_layers),)
        if self.moe is not None and self.mla is not None:
            return (
                (BLOCK_MLA_DENSE, self.n_dense_prefix),
                (BLOCK_MLA_MOE, self.n_layers - self.n_dense_prefix),
            )
        if self.moe is not None:
            return (
                (BLOCK_ATTN_DENSE, self.n_dense_prefix),
                (BLOCK_ATTN_MOE, self.n_layers - self.n_dense_prefix),
            )
        if self.mla is not None:
            return ((BLOCK_MLA_DENSE, self.n_layers),)
        return ((BLOCK_ATTN_DENSE, self.n_layers),)

    def param_count(self) -> int:
        """Parameter count N (used for 6*N*D roofline check); exact for
        RMSNorm attention and Mamba2 blocks."""
        d = self.d_model
        n = 0
        n += self.vocab * d                       # embedding
        if not self.tie_embeddings:
            n += self.vocab * d                   # lm head
        for kind, count in self.block_pattern:
            if count == 0:
                continue
            n += count * self._block_params(kind)
        if self.shared_period:                    # zamba2 shared attn+MLP block
            n += self._attn_params() + self._mlp_params(self.d_ff) + 2 * d
        n += d                                    # final norm
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts count)."""
        d = self.d_model
        n = self.vocab * d
        if not self.tie_embeddings:
            n += self.vocab * d
        for kind, count in self.block_pattern:
            if count == 0:
                continue
            n += count * self._block_params(kind, active_only=True)
        if self.shared_period:
            n += self._attn_params() + self._mlp_params(self.d_ff) + 2 * d
        n += d
        return n

    def _attn_params(self) -> int:
        d = self.d_model
        if self.mla is not None:
            m = self.mla
            h = self.attn.n_heads
            p = d * m.q_lora_rank
            p += m.q_lora_rank * h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
            p += d * (m.kv_lora_rank + m.qk_rope_head_dim)
            p += m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
            p += h * m.v_head_dim * d
            return p
        a = self.attn
        p = d * a.n_heads * a.head_dim            # q
        p += 2 * d * a.n_kv_heads * a.head_dim    # k, v
        p += a.n_heads * a.head_dim * d           # o
        if a.qk_norm:
            p += 2 * a.head_dim                   # q_norm, k_norm
        return p

    def _mlp_params(self, d_ff: int) -> int:
        k = 3 if self.gated_mlp else 2            # gated: w_in, w_gate, w_out
        return k * self.d_model * d_ff

    def _block_params(self, kind: str, active_only: bool = False) -> int:
        d = self.d_model
        norm_p = 2 * d
        if kind == BLOCK_MAMBA:
            s = self.ssm
            di = s.d_inner(d)
            nh = s.n_heads(d)
            # in_proj produces [z, x, B, C, dt]: di + di + 2*n_groups*d_state + nh
            n_groups = 1
            conv_ch = di + 2 * n_groups * s.d_state
            p = d * (2 * di + 2 * n_groups * s.d_state + nh)
            p += (s.d_conv + 1) * conv_ch                     # conv1d + bias
            p += nh * 3                                       # dt_bias, A_log, D
            p += di                                           # gate norm
            p += di * d                                       # out proj
            return p + d                                      # + pre-norm
        if kind == BLOCK_HYBRID_SHARED:
            # zamba2: per-layer params are the mamba block only; the shared
            # attention+MLP block is weight-tied (counted once, below).
            return self._block_params(BLOCK_MAMBA)
        p = self._attn_params() + norm_p
        if kind in (BLOCK_ATTN_MOE, BLOCK_MLA_MOE):
            m = self.moe
            per_expert = self._mlp_params(m.d_ff_expert)
            n_exp = m.top_k if active_only else m.n_experts
            p += n_exp * per_expert
            p += m.n_shared_experts * per_expert
            p += self.d_model * m.n_experts                   # router
        else:
            p += self._mlp_params(self.d_ff)
        return p

    # ---- reduced smoke variant ---------------------------------------------

    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant: 2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, 256)
        attn = None
        if self.attn is not None:
            a = self.attn
            nh = min(a.n_heads, 4)
            nkv = max(1, min(a.n_kv_heads, nh))
            # keep the MQA/GQA character: preserve ratio where possible
            if a.n_kv_heads < a.n_heads:
                nkv = max(1, nh * a.n_kv_heads // a.n_heads)
            attn = dataclasses.replace(
                a, n_heads=nh, n_kv_heads=nkv, head_dim=min(a.head_dim, 64),
                window=min(a.window, 64) if a.window else 0)
        moe = None
        if self.moe is not None:
            m = self.moe
            # capacity_factor 4.0: no token dropping at smoke scale, so
            # decode-vs-forward consistency tests see exact semantics
            # (capacity overflow is a train-scale behavior).
            moe = dataclasses.replace(
                m, n_experts=min(m.n_experts, 4), top_k=min(m.top_k, 2),
                d_ff_expert=min(m.d_ff_expert, 128),
                n_shared_experts=min(m.n_shared_experts, 1),
                capacity_factor=4.0)
        ssm = None
        if self.ssm is not None:
            s = self.ssm
            ssm = dataclasses.replace(
                s, d_state=min(s.d_state, 16), head_dim=min(s.head_dim, 32),
                chunk=16)
        mla = None
        if self.mla is not None:
            mla = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                            qk_nope_head_dim=32, qk_rope_head_dim=16,
                            v_head_dim=32)
        return dataclasses.replace(
            self, n_layers=2, d_model=d, d_ff=min(self.d_ff, 512),
            vocab=min(self.vocab, 1024), attn=attn, moe=moe, ssm=ssm, mla=mla,
            n_dense_prefix=min(self.n_dense_prefix, 1),
            shared_period=2 if self.shared_period else 0,
            n_prefix_embeds=min(self.n_prefix_embeds, 8),
            param_dtype="float32")


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        # import all config modules lazily
        from repro.configs import ALL_ARCHS  # noqa: F401
    return _REGISTRY[name]


def list_archs() -> list:
    from repro.configs import ALL_ARCHS  # noqa: F401
    return sorted(_REGISTRY)


def supports_shape(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch, shape) is runnable; returns (ok, reason-if-not).

    Encoder-only archs have no decode step.  ``long_500k`` decode requires
    sub-quadratic attention over the 524k context: SSM / hybrid always
    qualify; dense archs qualify only with a sliding-window variant
    (gemma3's native 5:1 local:global pattern).  See DESIGN.md.
    """
    if shape.kind == "decode" and cfg.encoder_only:
        return False, "encoder-only architecture has no autoregressive decode"
    if shape.name == "long_500k":
        subquadratic = (
            cfg.arch_type in ("ssm", "hybrid")
            or (cfg.attn is not None and cfg.attn.window > 0)
        )
        if not subquadratic:
            return False, ("full-attention architecture without sliding-window "
                           "variant; 524k KV cache rules it out (DESIGN.md)")
    return True, ""
