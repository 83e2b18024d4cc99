"""granite-4.0-h-micro [hybrid_ssd] — 40L d2048 ff8192 V100352: 36 Mamba-2
SSD mixers (64 heads of 64, state 128, conv 4 with bias, chunk 256) and 4
GQA attention mixers (32 query / 8 KV heads of 64, no positional encoding)
at layers 5, 15, 25 and 35, each mixer followed by a SwiGLU MLP.  Embedding
x12, sub-layer outputs x0.22, attention scale 1/64, logits /8, the head
tied to the embedding.  Full attention layers: not sub-quadratic.
[huggingface.co/ibm-granite/granite-4.0-h-micro, granitemoehybrid]
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid_ssd",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab_size=100352, mlp="swiglu",
    rotary_pct=0.0, nope=True, attn_scale=0.015625,
    attn_period=10, attn_offset=5,
    ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_chunk=256,
    conv_width=4, conv_bias=True,
    embed_mult=12.0, residual_mult=0.22, logits_scaling=8.0,
    tie_embeddings=True,
)
