"""zamba2-1.2b — Zyphra's Zamba2-1.2B, on the Zamba2 hybrid block of
``models/hybrid.py``: 38 Mamba2 layers at d_model 2048 and one shared
transformer block over [hidden, embeddings] (4096 wide, 32 heads of 128),
vocab 32000.  [arXiv:2411.15242]

Unverified (no config.json of this model was at hand; ``zamba2-7b``'s
published settings stand in): the hybrid layers (every sixth), the
adapter (rank 128 on the MLP only), one B/C group, chunk 128, state 64,
an exact-GELU MLP of 8192, the softmax scale (head_dim / 2) ** -0.5 and
tied embeddings.
"""
from repro.configs.base import ModelConfig, register

HEAD_DIM = 128

CONFIG = ModelConfig(
    name="zamba2-1.2b",
    family="hybrid",
    num_layers=38,
    d_model=2048,
    num_heads=32,
    num_kv_heads=32,
    head_dim=HEAD_DIM,
    d_ff=8192,
    vocab_size=32000,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=128,
    ssm_conv=4,
    hybrid_layer_ids=(6, 12, 18, 24, 30, 36),
    num_mem_blocks=1,
    adapter_rank=128,
    attn_input_width=2 * 2048,
    attn_scale=(HEAD_DIM / 2) ** -0.5,
    gated_mlp=True,
    act="gelu",
    rope_theta=10_000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="arXiv:2411.15242; widths of the shared block and its "
           "placement unverified",
)

SMOKE = CONFIG.replace(
    name="zamba2-1.2b-smoke",
    num_layers=4,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    head_dim=32,
    d_ff=128,
    vocab_size=256,
    ssm_state=16,
    ssm_head_dim=16,
    ssm_chunk=32,
    hybrid_layer_ids=(2,),
    adapter_rank=8,
    attn_input_width=128,
    attn_scale=(32 / 2) ** -0.5,
)

register(CONFIG, SMOKE)
