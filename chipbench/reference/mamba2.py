"""Plain float32 Mamba2 language model (arXiv:2405.21060), one row at a
time.

Block: RMSNorm -> in projections z, x, B, C, dt -> causal depthwise conv
(width ``ssm_conv``) and SiLU on x, B and C -> SSD -> skip ``D * x`` ->
gated RMSNorm ``norm(y * silu(z))`` -> out projection -> residual.  One
B/C group.  The SSD is written in its quadratic ("dual") form,
``y[t] = sum_{s<=t} (C[t].B[s]) exp(sum_{r=s+1..t} dt[r] A) dt[s] x[s]``,
which is the recurrence ``h[t] = exp(dt[t] A) h[t-1] + dt[t] B[t] x[t]``,
``y[t] = C[t].h[t]`` summed out: no chunks, no state carried.

Parameters are a flat dict ``{"a/b/c": array}`` whose names and shapes
``param_spec`` lists; per-layer leaves are stacked on a leading layer axis.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.numerics import F32, Numerics

Params = Dict[str, jax.Array]
# (name, shape, init, dtype); init: normal | fan_in | ones | zeros |
# a_log | dt_bias
Spec = List[Tuple[str, Tuple[int, ...], str, str]]


def block_spec(m: Dict[str, Any], prefix: str = "blocks/") -> Spec:
    d, n_l = m["d_model"], m["num_layers"]
    din = m["ssm_expand"] * d
    N, W = m["ssm_state"], m["ssm_conv"]
    H = din // m["ssm_head_dim"]
    dt = m["param_dtype"]
    rows = [
        ("ln", (n_l, d), "ones", dt),
        ("w_z", (n_l, d, din), "fan_in", dt),
        ("w_x", (n_l, d, din), "fan_in", dt),
        ("w_B", (n_l, d, N), "fan_in", dt),
        ("w_C", (n_l, d, N), "fan_in", dt),
        ("w_dt", (n_l, d, H), "fan_in", dt),
        ("conv_x", (n_l, W, din), "fan_in", dt),
        ("conv_B", (n_l, W, N), "fan_in", dt),
        ("conv_C", (n_l, W, N), "fan_in", dt),
        ("conv_x_b", (n_l, din), "zeros", dt),
        ("conv_B_b", (n_l, N), "zeros", dt),
        ("conv_C_b", (n_l, N), "zeros", dt),
        ("A_log", (n_l, H), "a_log", "float32"),
        ("D", (n_l, H), "ones", "float32"),
        ("dt_bias", (n_l, H), "dt_bias", "float32"),
        ("norm", (n_l, din), "ones", dt),
        ("w_out", (n_l, din, d), "fan_in", dt),
    ]
    return [(prefix + n, s, i, t) for n, s, i, t in rows]


def embed_spec(m: Dict[str, Any]) -> Spec:
    V, d, dt = m["vocab_size"], m["d_model"], m["param_dtype"]
    out = [("embed/tok", (V, d), "normal", dt)]
    if not m["tie_embeddings"]:
        out.append(("embed/lm_head", (V, d), "fan_in", dt))
    return out


def param_spec(m: Dict[str, Any]) -> Spec:
    return (embed_spec(m) + block_spec(m)
            + [("ln_f", (m["d_model"],), "ones", m["param_dtype"])])


def sub(params: Params, prefix: str) -> Params:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """x: (T, C); w: (W, C).  y[t] = sum_k w[k] x[t - (W-1) + k]."""
    W = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1]), x.dtype), x])
    T = x.shape[0]
    y = sum(w[k] * xp[k:k + T] for k in range(W))
    return jax.nn.silu(y + b)


def ssd_quadratic(x, dt, A, B, C, nx: Numerics, block: int = 256):
    """x: (T, H, P); dt: (T, H); A: (H,); B, C: (T, N) -> (T, H, P).

    Output rows are taken ``block`` at a time, each against the positions
    up to its last: the terms above the diagonal, which are zero, are
    computed for the diagonal blocks alone."""
    T = x.shape[0]
    cs = jnp.cumsum(dt * A[None, :], axis=0)              # (T, H)
    xdt = x * dt[:, :, None]
    out = []
    for t0 in range(0, T, block):
        t1 = min(t0 + block, T)
        seg = cs[t0:t1].T[:, :, None] - cs[:t1].T[:, None, :]  # (H, t, s)
        causal = (jnp.arange(t0, t1)[:, None]
                  >= jnp.arange(t1)[None, :])
        decay = jnp.exp(jnp.where(causal[None], seg, -jnp.inf))
        cb = nx.mm(C[t0:t1], B[:t1].T)                     # (t, s)
        out.append(nx.einsum("hts,shp->thp", decay * cb[None], xdt[:t1]))
    return jnp.concatenate(out, axis=0)


def mamba_block(p: Params, x, m: Dict[str, Any], nx: Numerics):
    """One layer on one row.  x: (T, d) -> (T, d)."""
    eps = m["norm_eps"]
    T = x.shape[0]
    P = m["ssm_head_dim"]
    h = rmsnorm(x, p["ln"], eps)
    z = nx.mm(h, p["w_z"])
    xs = causal_conv(nx.mm(h, p["w_x"]), p["conv_x"], p["conv_x_b"])
    Bm = causal_conv(nx.mm(h, p["w_B"]), p["conv_B"], p["conv_B_b"])
    Cm = causal_conv(nx.mm(h, p["w_C"]), p["conv_C"], p["conv_C_b"])
    dt = jax.nn.softplus(nx.mm(h, p["w_dt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(T, -1, P)
    y = ssd_quadratic(xh, dt, A, Bm, Cm, nx) + xh * p["D"][None, :, None]
    y = rmsnorm(y.reshape(T, -1) * jax.nn.silu(z), p["norm"], eps)
    return x + nx.mm(y, p["w_out"])


def run_stack(blocks: Params, x, m: Dict[str, Any], nx: Numerics):
    """Scan the stacked layers of ``blocks`` over one row."""
    def body(h, p_l):
        return jax.checkpoint(
            lambda pp, hh: mamba_block(pp, hh, m, nx))(p_l, h), None
    x, _ = lax.scan(body, x, blocks)
    return x


def hidden(params: Params, tokens, m: Dict[str, Any], nx: Numerics = F32):
    """tokens: (T,) -> final-norm hidden states (T, d)."""
    x = params["embed/tok"][tokens]
    x = run_stack(sub(params, "blocks/"), x, m, nx)
    return rmsnorm(x, params["ln_f"], m["norm_eps"])


def vocab_weight(params: Params, m: Dict[str, Any]):
    return params["embed/tok" if m["tie_embeddings"] else "embed/lm_head"]


def logits(params: Params, tokens, m: Dict[str, Any], nx: Numerics = F32):
    return nx.mm(hidden(params, tokens, m, nx), vocab_weight(params, m).T)


def nll_sum(logits_, labels, mask):
    """Sum over the row of mask * (logsumexp - logit of the label)."""
    lse = jax.nn.logsumexp(logits_, axis=-1)
    tgt = jnp.take_along_axis(logits_, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - tgt) * mask)


def init_leaf(key, shape, init: str, dtype: str):
    if init == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif init == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif init == "normal":
        x = jax.random.normal(key, shape, jnp.float32) * 0.02
    elif init == "fan_in":
        x = (jax.random.normal(key, shape, jnp.float32)
             / math.sqrt(max(shape[-2] if len(shape) >= 2 else shape[-1],
                             1)))
    elif init == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif init == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32, 0.001, 0.1)
        x = u + jnp.log(-jnp.expm1(-u))
    else:
        raise ValueError(f"unknown init {init!r}")
    return x.astype(dtype)
