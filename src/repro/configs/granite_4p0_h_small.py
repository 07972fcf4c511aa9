"""granite-4.0-h-small [hybrid_moe]: 40L d_model=4096; layers 5, 15, 25, 35
GQA attention 32H (kv=8, no positional encoding), the other 36 Mamba-2 (128
heads x 64, state 128); every layer MoE 72 experts top-10 (width 768) plus a
shared SwiGLU of 1536; vocab=100352 tied; granite multipliers.
[hf:ibm-granite/granite-4.0-h-small]
"""
from repro.configs.base import ArchConfig, MoEConfig, SSMConfig

_ATTN = (5, 15, 25, 35)

CONFIG = ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    source="hf:ibm-granite/granite-4.0-h-small",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=0,                     # every layer's MLP is the MoE block
    vocab=100352,
    norm_eps=1e-5,
    moe=MoEConfig(n_experts=72, top_k=10, d_ff_expert=768, d_ff_shared=1536),
    ssm=SSMConfig(state_dim=128, expand=2, conv_width=4, head_dim=64,
                  version=2),
    layer_types=tuple("attention" if i in _ATTN else "mamba"
                      for i in range(40)),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    use_rope=False,
    tie_embeddings=True,
    fed_mode="zero",            # 32B total params: client = pod
)
