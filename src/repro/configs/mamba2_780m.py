"""mamba2-780m [ssm] — SSD (state-space duality), attention-free.
[arXiv:2405.21060; state-spaces/mamba2-780m config.json, Mamba2 layer
defaults of mamba_ssm/modules/mamba2.py: RMSNorm eps 1e-5, float32
residual, SSD chunk 256, one B/C group]"""
from repro.configs.base import ArchConfig, SSMConfig, register

ARCH = register(ArchConfig(
    name="mamba2-780m",
    arch_type="ssm",
    source="arXiv:2405.21060",
    n_layers=48,
    d_model=1536,
    d_ff=0,
    vocab=50280,
    ssm=SSMConfig(d_state=128, head_dim=64, chunk=256),
    norm="rmsnorm",
    norm_eps=1e-5,
    residual_in_fp32=True,
    tie_embeddings=True,
))
