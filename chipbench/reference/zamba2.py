"""Plain float32 Zamba2 language model (arXiv:2411.15242; the
``transformers`` Zamba2 code), one row at a time.

Layers 0 .. n-1 are Mamba2 layers; a layer of ``hybrid_layer_ids`` is
hybrid.  Mamba2 layer: RMSNorm -> projections z, x, B, C, dt -> causal
depthwise conv and SiLU on x, B, C -> SSD over ``ssm_groups`` B/C groups
(head h reads group h // (H / G)) -> skip ``D * x`` -> ``y * silu(z)``
normalised per group of d_inner / G channels -> out projection ->
residual.  Before the i-th hybrid layer's Mamba2 layer, shared block
``i % num_mem_blocks`` runs over ``[hidden, original embeddings]``:
RMSNorm -> causal multi-head attention with rope over the whole head and
softmax scale ``attn_scale`` -> RMSNorm -> gated exact-GELU MLP whose gate
and up projections add the layer's rank-``adapter_rank`` adapter -> the
layer's ``linear``; the result is added to the Mamba2 layer's input before
its norm, not to its residual.  There is no residual inside the block.

Departures from the published code: the fused projections are held split
(``in_proj`` as z, x, B, C, dt; ``gate_up_proj`` and its adapter's second
matrix as gate and up halves, in that order), as the program stores them;
the SSD is its quadratic form, and the SSD and attention are taken in
blocks of output rows, neither of which changes the result beyond
rounding.  Parameter names and shapes are the program's.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.mamba2 import causal_conv, embed_spec, rmsnorm, sub
from chipbench.reference.numerics import F32, Numerics


def param_spec(m: Dict[str, Any]):
    d, n_l, dt = m["d_model"], m["num_layers"], m["param_dtype"]
    din = m["ssm_expand"] * d
    N, W, G = m["ssm_state"], m["ssm_conv"], m["ssm_groups"]
    H = din // m["ssm_head_dim"]
    nb, n_app = m["num_mem_blocks"], len(m["hybrid_layer_ids"])
    dq = m["num_heads"] * m["head_dim"]
    dkv = m["num_kv_heads"] * m["head_dim"]
    da, f, r = m["attn_input_width"], m["d_ff"], m["adapter_rank"]
    blocks = [
        ("ln", (n_l, d), "ones", dt),
        ("w_z", (n_l, d, din), "fan_in", dt),
        ("w_x", (n_l, d, din), "fan_in", dt),
        ("w_B", (n_l, d, G * N), "fan_in", dt),
        ("w_C", (n_l, d, G * N), "fan_in", dt),
        ("w_dt", (n_l, d, H), "fan_in", dt),
        ("conv_x", (n_l, W, din), "fan_in", dt),
        ("conv_B", (n_l, W, G * N), "fan_in", dt),
        ("conv_C", (n_l, W, G * N), "fan_in", dt),
        ("conv_x_b", (n_l, din), "zeros", dt),
        ("conv_B_b", (n_l, G * N), "zeros", dt),
        ("conv_C_b", (n_l, G * N), "zeros", dt),
        ("A_log", (n_l, H), "a_log", "float32"),
        ("D", (n_l, H), "ones", "float32"),
        ("dt_bias", (n_l, H), "dt_bias", "float32"),
        ("norm", (n_l, din), "ones", dt),
        ("w_out", (n_l, din, d), "fan_in", dt),
    ]
    shared = [
        ("ln_in", (nb, da), "ones", dt),
        ("attn/wq", (nb, da, dq), "fan_in", dt),
        ("attn/wk", (nb, da, dkv), "fan_in", dt),
        ("attn/wv", (nb, da, dkv), "fan_in", dt),
        ("attn/wo", (nb, dq, d), "fan_in", dt),
        ("ln_ff", (nb, d), "ones", dt),
        ("mlp/w_up", (nb, d, f), "fan_in", dt),
        ("mlp/w_down", (nb, f, d), "fan_in", dt),
        ("mlp/w_gate", (nb, d, f), "fan_in", dt),
    ]
    hybrid = [("linear", (n_app, d, d), "fan_in", dt)]
    if r:
        hybrid += [("adapter/a", (n_app, d, r), "fan_in", dt),
                   ("adapter/b_gate", (n_app, r, f), "fan_in", dt),
                   ("adapter/b_up", (n_app, r, f), "fan_in", dt)]
    return (embed_spec(m)
            + [("blocks/" + n, s, i, t) for n, s, i, t in blocks]
            + [("shared/" + n, s, i, t) for n, s, i, t in shared]
            + [("hybrid/" + n, s, i, t) for n, s, i, t in hybrid]
            + [("ln_f", (d,), "ones", dt)])


def _row_blocks(T: int, block: int) -> int:
    return block if T % block == 0 else T


def ssd_grouped(x, dt, A, B, C, nx: Numerics, block: int = 256):
    """The SSD in its quadratic form with G B/C groups.  x: (T, H, P);
    dt: (T, H); A: (H,); B, C: (T, G, N) -> (T, H, P):
    ``y[t] = sum_{s<=t} (C[t].B[s]) exp(sum_{r=s+1..t} dt[r] A) dt[s] x[s]``
    with head h on group h // (H / G).  Output rows are taken ``block`` at
    a time (``lax.map``, each block recomputed for the backward pass), each
    against every position, those after it masked."""
    T, H, P = x.shape
    G = B.shape[1]
    block = _row_blocks(T, block)
    cs = jnp.cumsum(dt * A[None, :], axis=0).reshape(T, G, H // G)
    xdt = (x * dt[:, :, None]).reshape(T, G, H // G, P)

    @jax.checkpoint
    def rows(i):
        t0 = i * block
        seg = lax.dynamic_slice_in_dim(cs, t0, block)[:, None] - cs[None]
        causal = (t0 + jnp.arange(block))[:, None] >= jnp.arange(T)[None]
        decay = jnp.exp(jnp.where(causal[:, :, None, None], seg, -jnp.inf))
        cb = nx.einsum("tgn,sgn->tsg",
                       lax.dynamic_slice_in_dim(C, t0, block), B)
        return nx.einsum("tsgk,sgkp->tgkp", decay * cb[..., None], xdt)
    return lax.map(rows, jnp.arange(T // block)).reshape(T, H, P)


def mamba_layer(p, x, m: Dict[str, Any], nx: Numerics, extra=None):
    """One Mamba2 layer on one row; ``extra`` joins its input before the
    norm.  x: (T, d) -> (T, d)."""
    eps, T = m["norm_eps"], x.shape[0]
    P, G, N = m["ssm_head_dim"], m["ssm_groups"], m["ssm_state"]
    h = rmsnorm(x if extra is None else x + extra, p["ln"], eps)
    z = nx.mm(h, p["w_z"])
    xs = causal_conv(nx.mm(h, p["w_x"]), p["conv_x"], p["conv_x_b"])
    Bm = causal_conv(nx.mm(h, p["w_B"]), p["conv_B"], p["conv_B_b"])
    Cm = causal_conv(nx.mm(h, p["w_C"]), p["conv_C"], p["conv_C_b"])
    dt = jax.nn.softplus(nx.mm(h, p["w_dt"]) + p["dt_bias"])
    xh = xs.reshape(T, -1, P)
    y = ssd_grouped(xh, dt, -jnp.exp(p["A_log"]), Bm.reshape(T, G, N),
                    Cm.reshape(T, G, N), nx)
    y = y + xh * p["D"][None, :, None]
    gated = (y.reshape(T, -1) * jax.nn.silu(z)).reshape(T, G, -1)
    y = (gated * lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True) + eps)
         ).reshape(T, -1) * p["norm"]
    return x + nx.mm(y, p["w_out"])


def rope(x, theta: float):
    """x: (T, heads, D), rotated over the whole head: the two halves of D
    as the cosine and sine parts (``rotate_half``)."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


def causal_attention(q, k, v, scale: float, nx: Numerics, block: int = 512):
    """q, k, v: (T, heads, D) -> (T, heads, D); query rows ``block`` at a
    time (``lax.map``, each block recomputed for the backward pass), each
    against every position, those after it masked."""
    T = q.shape[0]
    block = _row_blocks(T, block)

    @jax.checkpoint
    def rows(i):
        t0 = i * block
        s = nx.einsum("thd,shd->hts",
                      lax.dynamic_slice_in_dim(q, t0, block), k) * scale
        causal = (t0 + jnp.arange(block))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(causal[None], s, -jnp.inf)
        return nx.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return lax.map(rows, jnp.arange(T // block)).reshape(q.shape)


def shared_block(sp, hp, x, emb, m: Dict[str, Any], nx: Numerics):
    """A hybrid layer's shared block and linear map on one row: what it
    adds to the Mamba2 layer's input.  x, emb: (T, d) -> (T, d)."""
    eps, T, Dh = m["norm_eps"], x.shape[0], m["head_dim"]
    h = rmsnorm(jnp.concatenate([x, emb], -1), sp["ln_in"], eps)
    q = rope(nx.mm(h, sp["attn/wq"]).reshape(T, -1, Dh), m["rope_theta"])
    k = rope(nx.mm(h, sp["attn/wk"]).reshape(T, -1, Dh), m["rope_theta"])
    v = nx.mm(h, sp["attn/wv"]).reshape(T, -1, Dh)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = causal_attention(q, k, v, m["attn_scale"], nx).reshape(T, -1)
    h = rmsnorm(nx.mm(o, sp["attn/wo"]), sp["ln_ff"], eps)
    gate = nx.mm(h, sp["mlp/w_gate"])
    up = nx.mm(h, sp["mlp/w_up"])
    if "adapter/a" in hp:
        r = nx.mm(h, hp["adapter/a"])
        gate = gate + nx.mm(r, hp["adapter/b_gate"])
        up = up + nx.mm(r, hp["adapter/b_up"])
    y = nx.mm(jax.nn.gelu(gate, approximate=False) * up, sp["mlp/w_down"])
    return nx.mm(y, hp["linear"])


def _layers(blocks, x, lo: int, hi: int, m, nx, extra=None):
    """Mamba2 layers lo .. hi-1 (scanned, each rematerialised)."""
    layer = jax.checkpoint(lambda p, h, ex: mamba_layer(p, h, m, nx, ex))

    def body(h, p_l):
        return layer(p_l, h, extra), None
    x, _ = lax.scan(body, x, {k: v[lo:hi] for k, v in blocks.items()})
    return x


def hidden(params, tokens, m: Dict[str, Any], nx: Numerics = F32):
    """tokens: (T,) -> final-norm hidden states (T, d)."""
    x = emb = params["embed/tok"][tokens]
    blocks, shared, hybrid = (sub(params, "blocks/"), sub(params, "shared/"),
                              sub(params, "hybrid/"))
    block = jax.checkpoint(
        lambda sp, hp, h, e: shared_block(sp, hp, h, e, m, nx))
    lo = 0
    for i, h_id in enumerate(m["hybrid_layer_ids"]):
        if h_id > lo:
            x = _layers(blocks, x, lo, h_id, m, nx)
        t = block({k: v[i % m["num_mem_blocks"]] for k, v in shared.items()},
                  {k: v[i] for k, v in hybrid.items()}, x, emb)
        x = _layers(blocks, x, h_id, h_id + 1, m, nx, extra=t)
        lo = h_id + 1
    if m["num_layers"] > lo:
        x = _layers(blocks, x, lo, m["num_layers"], m, nx)
    return rmsnorm(x, params["ln_f"], m["norm_eps"])


def logits(params, tokens, m: Dict[str, Any], nx: Numerics = F32):
    w = params["embed/tok" if m["tie_embeddings"] else "embed/lm_head"]
    return nx.mm(hidden(params, tokens, m, nx), w.T)
