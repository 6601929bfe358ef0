"""granite-4.0-h-small [hybrid, 32B-A9B]: 40L d4096, 36 Mamba-2 layers and 4
GQA attention layers (32 query / 8 KV heads of 128, NoPE, score scale 1/128);
every layer a sparse MoE of 72 experts of width 768, top-10, beside one
shared SwiGLU expert of 1536.  Mamba-2: 128 heads of 64, d_state 128, one
group, conv 4 with bias, expand 2.  RMSNorm 1e-5; embeddings x12, residual
branches x0.22, logits /16; tied; vocab 100352.
[https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json]
"""
from .base import ModelConfig, register

_M, _A = "mamba", "attention"

CONFIG = register(ModelConfig(
    name="granite-4.0-h-small",
    family="lm",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv=8,
    d_ff=768,
    vocab=100352,
    layer_types=(_M,) * 5 + (_A,) + ((_M,) * 9 + (_A,)) * 3 + (_M,) * 4,
    use_rope=False,
    attn_scale=0.0078125,
    moe_experts=72,
    moe_topk=10,
    moe_dff=768,
    moe_shared_dff=1536,
    ssm_state=128,
    ssm_conv=4,
    ssm_expand=2,
    ssm_head=64,
    act="swiglu",
    norm_eps=1e-5,
    embed_mult=12.0,
    residual_mult=0.22,
    logits_div=16.0,
    tie_embeddings=True,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json",
    verified="hf",
))
