"""zamba2-7b [hybrid] — Zamba2-7B (Zyphra; HF Zyphra/Zamba2-7B-Instruct
config.json, arXiv:2411.15242): 81 Mamba-2 layers, d_model=3584,
d_inner=7168 in 112 heads of 64, d_state=64, 2 B/C groups; two
weight-shared [attn + mlp] blocks that alternate over the 13 hybrid
layers, on concat(x, embedding) (width 7168, 32 heads of 224, RoPE
theta 10000), a gated-GELU MLP of 14336 with a rank-128 LoRA adapter per
application, and a per-application linear into the mamba input."""
import dataclasses

from repro.configs.base import AttentionPattern, ModelConfig, SSMConfig

HYBRID_LAYERS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab=32000,
    attn=AttentionPattern(kind="full"),
    ssm=SSMConfig(d_state=64, head_dim=64, expand=2, d_conv=4, chunk=256,
                  n_groups=2),
    hybrid_layers=HYBRID_LAYERS,
    n_shared_blocks=2,
    adapter_rank=128,
    mlp_act="gelu",
    rope_theta=10000.0,
    tie_embeddings=True,
)


def smoke() -> ModelConfig:
    """Every part of the mechanism at tiny widths: two shared blocks over
    three applications (A, B, A), two B/C groups, a rank-4 adapter."""
    return dataclasses.replace(
        CONFIG, name="zamba2-smoke", n_layers=6, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=32, d_ff=128, vocab=256,
        ssm=SSMConfig(d_state=16, head_dim=8, expand=2, d_conv=4, chunk=16,
                      n_groups=2),
        hybrid_layers=(1, 3, 5), adapter_rank=4)
