"""Model operations counted from shapes, for MFU.

Counts what the model needs, not what a program executes: no recomputed
(rematerialised) work, one multiply-add as two operations.  The SSD is
counted as the chunked algorithm computes it (chunk ``ssm_chunk``): the
C.B products and the causal output inside each chunk in full, the chunk
states and the output read from them.  Training counts 3x the forward
(the backward pass is twice the forward).
"""
from __future__ import annotations

from typing import Any, Dict

M = Dict[str, Any]


def _dims(m: M):
    d = m["d_model"]
    din = m["ssm_expand"] * d
    H = din // m["ssm_head_dim"]
    return d, din, m["ssm_state"], H, m["ssm_head_dim"], m["ssm_conv"]


def mamba_matmul_params(m: M) -> int:
    d, din, N, H, _, _ = _dims(m)
    return d * (2 * din + 2 * N + H) + din * d


def mamba_layer_flops(m: M) -> float:
    """Forward operations of one Mamba2 layer for one token."""
    d, din, N, H, P, W = _dims(m)
    Q = m["ssm_chunk"]
    return (2 * mamba_matmul_params(m) + 2 * W * (din + 2 * N)
            + 2 * Q * N + 2 * Q * H * P + 4 * H * P * N)


def logits_flops(m: M) -> float:
    return 2 * m["vocab_size"] * m["d_model"]


def forward_flops(m: M, logits: bool = True) -> float:
    """Forward operations for one token."""
    ops = m["num_layers"] * mamba_layer_flops(m)
    return ops + (logits_flops(m) if logits else 0.0)


def train_flops_per_token(m: M) -> float:
    return 3 * forward_flops(m)


def param_count(spec) -> int:
    n = 0
    for _, shape, _, _ in spec:
        k = 1
        for s in shape:
            k *= s
        n += k
    return n
