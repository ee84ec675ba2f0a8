"""granite-4.0-h-small — IBM Granite 4.0-H Small (32B-A9B): a hybrid of
Mamba-2 and NoPE GQA attention, every layer's FFN a 72-expert MoE with a
shared expert [hf:ibm-granite/granite-4.0-h-small, config.json].

Each period of ten layers holds nine Mamba-2 mixers and one attention
layer (layers 5, 15, 25, 35). Mamba-2: 128 heads of 64, d_state 128, one
group, conv 4 with bias, chunk 256. Attention: 32/8 heads; config.json
gives no head size, so d_head is d_model / n_heads = 128. Experts: width
768 (``intermediate_size``), 10 of 72 a token, softmax over the top-10
logits, plus a SwiGLU shared expert of width 1536. Multipliers: embedding
x12, residual x0.22, attention scale 1/128 (not 1/sqrt(128)), logits /16.
Tied embeddings, vocabulary 100,352, RMSNorm eps 1e-5.

``CONFIG_EP4`` is one chip of four that share each layer's experts
(expert parallelism): it holds 18 of the 72 and computes their part of
the routed result; the router still scores all 72.
"""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_head=128,
    d_ff=768,
    vocab=100352,
    positions="none",
    rope_theta=10000.0,   # stated in config.json; unused under NoPE
    tie_embeddings=True,
    attn_layer_period=10,
    attn_layer_offset=5,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_chunk=256,
    ssm_conv=4,
    n_experts=72,
    top_k=10,
    expert_d_ff=768,
    moe_layer_step=1,
    shared_d_ff=1536,
    moe_dispatch="dropless",  # grouped matmul over the held experts
    embed_mult=12.0,
    resid_mult=0.22,
    attn_mult=0.0078125,
    logits_div=16.0,
    long_context_ok=True,  # four attention layers in forty
    source="hf:ibm-granite/granite-4.0-h-small",
)

CONFIG_EP4 = dataclasses.replace(CONFIG, name="granite-4.0-h-small-ep4",
                                 n_experts_held=18)
