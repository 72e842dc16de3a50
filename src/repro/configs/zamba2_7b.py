"""zamba2-7b — Zyphra's Zamba2-7B(-Instruct): 81 Mamba2 layers (d_model
3584, 112 heads of 64, 2 B/C groups, state 64, conv 4, chunk 256), 13 of
them hybrid, sharing two transformer blocks used in turn (32 heads of 224
over [hidden, embeddings], 7168 wide; rope; gated exact-GELU MLP of 14336
with a rank-128 adapter on gate/up per hybrid layer).  Vocab 32000, tied.
[https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json;
arXiv:2411.15242]

``tie_embeddings`` is the ``Zamba2Config`` default (the config omits the
key).  The softmax scale is ``(attention_head_dim / 2) ** -0.5``, as in
the ``transformers`` Zamba2 attention.
"""
from repro.configs.base import ModelConfig, register

HEAD_DIM = 224

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=HEAD_DIM,
    d_ff=14336,
    vocab_size=32000,
    gated_mlp=True,
    act="gelu",
    rope_theta=10_000.0,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    ssm_conv=4,
    ssm_groups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    attn_input_width=2 * 3584,
    attn_scale=(HEAD_DIM / 2) ** -0.5,
    norm_eps=1e-5,
    tie_embeddings=True,
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct; "
           "arXiv:2411.15242",
)

# two shared blocks, two hybrid layers, two B/C groups
SMOKE = CONFIG.replace(
    name="zamba2-7b-smoke",
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
    hybrid_layer_ids=(1, 3),
    adapter_rank=8,
    attn_input_width=128,
    attn_scale=(32 / 2) ** -0.5,
)

register(CONFIG, SMOKE)
