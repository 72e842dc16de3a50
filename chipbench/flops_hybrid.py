"""Model operations of a Zamba2 hybrid counted from shapes, for MFU, as
``flops.py`` counts them: no recomputed work, one multiply-add as two
operations, training as 3x the forward.

A Mamba2 layer is ``flops.py``'s with ``ssm_groups`` B/C groups: the C.B
products are per group.  A hybrid layer adds its shared block and linear
map: the Q/K/V projections over ``attn_input_width`` features, causal
attention (scores and weighted values over the positions up to each
token: on average (seq_len + 1) / 2 of them), the o projection, the gated
MLP, the adapter's two products on gate and up, and the linear map.
"""
from __future__ import annotations

from typing import Any, Dict

M = Dict[str, Any]


def _dims(m: M):
    d = m["d_model"]
    din = m["ssm_expand"] * d
    H = din // m["ssm_head_dim"]
    GN = m["ssm_groups"] * m["ssm_state"]
    return d, din, GN, H, m["ssm_head_dim"], m["ssm_state"], m["ssm_conv"]


def mamba_matmul_params(m: M) -> int:
    d, din, GN, H, _, _, _ = _dims(m)
    return d * (2 * din + 2 * GN + H) + din * d


def mamba_layer_flops(m: M) -> float:
    """Forward operations of one Mamba2 layer for one token."""
    d, din, GN, H, P, N, W = _dims(m)
    Q = m["ssm_chunk"]
    return (2 * mamba_matmul_params(m) + 2 * W * (din + 2 * GN)
            + 2 * Q * GN + 2 * Q * H * P + 4 * H * P * N)


def shared_matmul_params(m: M) -> int:
    """Weights of one hybrid layer's products: the shared block (with its
    adapter) and the linear map."""
    d, da, f, r = (m["d_model"], m["attn_input_width"], m["d_ff"],
                   m["adapter_rank"])
    dq = m["num_heads"] * m["head_dim"]
    dkv = m["num_kv_heads"] * m["head_dim"]
    return (da * (dq + 2 * dkv) + dq * d + 3 * d * f
            + r * (d + 2 * f) + d * d)


def hybrid_extra_flops(m: M, seq_len: int) -> float:
    """Forward operations a hybrid layer adds for one token of a row of
    ``seq_len``."""
    dq = m["num_heads"] * m["head_dim"]
    return 2 * shared_matmul_params(m) + 2 * 2 * dq * (seq_len + 1) / 2


def forward_flops(m: M, seq_len: int, logits: bool = True) -> float:
    ops = (m["num_layers"] * mamba_layer_flops(m)
           + len(m["hybrid_layer_ids"]) * hybrid_extra_flops(m, seq_len))
    return ops + (2 * m["vocab_size"] * m["d_model"] if logits else 0.0)


def train_flops_per_token(m: M, seq_len: int) -> float:
    return 3 * forward_flops(m, seq_len)
