"""Granite-4.0-H-Micro — hybrid by layer pattern: 40 layers of hidden
size 2048, of which 36 are Mamba2 mixers (64 heads of width 64, state
128, one group, conv 4, chunk 256) and 4 are GQA attention mixers at
layers 5, 15, 25 and 35 (32 query heads, 8 KV heads, head size 64, no
positional encoding, scale 0.015625). Every mixer is followed by a
SwiGLU MLP of width 8192. Embeddings scaled by 12, both residual
branches by 0.22, logits divided by 8; tied embeddings; RMSNorm eps
1e-5. About 3.19 B parameters.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro
(config.json, model_type granitemoehybrid). Mamba2's dt limit is
granitemoehybrid's default, (0, inf): no clip.
"""

from repro.configs.base import ArchConfig, register

_ATTN = (5, 15, 25, 35)

CONFIG = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    activation="silu",
    gated_mlp=True,
    norm="rmsnorm",
    norm_eps=1e-5,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv=4,
    ssm_chunk=256,
    ssm_dt_limit=(0.0, float("inf")),
    layer_types=tuple("attention" if i in _ATTN else "mamba"
                      for i in range(40)),
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=0.015625,
    positions="nope",
    tie_embeddings=True,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
))
