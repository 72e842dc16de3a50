"""Plain float32 Zamba2 (arXiv:2411.15242; the ``transformers`` Zamba2
code): forward pass, loss and gradients in straightforward ``jax.numpy``,
one row at a time, under ``jax.default_matmul_precision("highest")``.  No
kernels, cache, chunking or batching; nothing of the program is imported.

Model: token embedding -> layers 0 .. n-1, each a Mamba2 layer; the i-th
layer of ``hybrid_layer_ids`` first runs shared block ``i %
num_mem_blocks`` over ``[hidden, original embeddings]`` and adds its
output, through the layer's ``linear`` map, to the Mamba2 layer's input
before its norm (the residual keeps the plain input) -> final RMSNorm ->
tied logits.

* Mamba2 layer: RMSNorm; projections z, x, B, C, dt; causal depthwise conv
  of width ``ssm_conv`` and SiLU on x, B, C; the SSD recurrence
  ``h[t] = exp(dt[t] A) h[t-1] + dt[t] B[t] x[t]``, ``y[t] = C[t] h[t]``,
  written in its quadratic form, with head h reading B/C group
  h // (H / G); skip ``D * x``; ``y * silu(z)`` normalised per group of
  d_inner / G channels (``RMSNormGated``); out projection; residual.
* Shared block: RMSNorm (width ``attn_input_width``); causal multi-head
  attention with rope over the whole head (``rotate_half``) and scores
  scaled by ``attn_scale``; o projection to d_model; RMSNorm; gated MLP
  ``gelu(gate) * up`` (exact GELU) whose gate and up add the layer's
  low-rank adapter ``(h @ a) @ b``; down projection.  No residual inside.

Departures from the published code: ``in_proj`` is held split into z, x,
B, C and dt, and ``gate_up_proj`` and the adapter's second matrix into
their gate and up halves (in that order), as the program stores them.
Parameters are the program's tree (``params``), read by name.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax


def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv(x, w, b):
    """x: (T, C); w: (W, C).  y[t] = sum_k w[k] x[t - (W-1) + k]."""
    W, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(w[k] * xp[k:k + T] for k in range(W)) + b)


def ssd(x, dt, A, B, C):
    """x: (T, H, P); dt: (T, H); A: (H,); B, C: (T, G, N) -> (T, H, P).
    y[t] = sum_{s<=t} (C[t].B[s]) exp(sum_{r=s+1..t} dt[r] A) dt[s] x[s],
    head h on group h // (H / G)."""
    T, H, _ = x.shape
    G = B.shape[1]
    g_of_h = jnp.arange(H) // (H // G)
    cs = jnp.cumsum(dt * A[None, :], axis=0)                    # (T, H)
    seg = cs.T[:, :, None] - cs.T[:, None, :]                   # (H, t, s)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    decay = jnp.exp(jnp.where(causal[None], seg, -jnp.inf))
    cb = jnp.einsum("tgn,sgn->gts", C, B)[g_of_h]               # (H, t, s)
    return jnp.einsum("hts,shp->thp", decay * cb, x * dt[:, :, None])


def mamba_layer(p, x, cfg, extra=None):
    T, eps = x.shape[0], cfg.norm_eps
    G, N, P = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    h = rmsnorm(x if extra is None else x + extra, p["ln"], eps)
    z = h @ p["w_z"]
    xs = causal_conv(h @ p["w_x"], p["conv_x"], p["conv_x_b"])
    B = causal_conv(h @ p["w_B"], p["conv_B"], p["conv_B_b"]).reshape(T, G, N)
    C = causal_conv(h @ p["w_C"], p["conv_C"], p["conv_C_b"]).reshape(T, G, N)
    dt = jax.nn.softplus(h @ p["w_dt"] + p["dt_bias"])
    xh = xs.reshape(T, -1, P)
    y = ssd(xh, dt, -jnp.exp(p["A_log"]), B, C) + xh * p["D"][None, :, None]
    y = (y.reshape(T, -1) * jax.nn.silu(z)).reshape(T, G, -1)
    y = rmsnorm(y, p["norm"].reshape(G, -1), eps).reshape(T, -1)
    return x + y @ p["w_out"]


def rope(x, theta):
    """x: (T, heads, D); positions 0 .. T-1."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def shared_block(sp, hp, x, emb, cfg):
    T, Dh, eps = x.shape[0], cfg.head_dim, cfg.norm_eps
    a = sp["attn"]
    h = rmsnorm(jnp.concatenate([x, emb], -1), sp["ln_in"], eps)
    q = rope((h @ a["wq"]).reshape(T, -1, Dh), cfg.rope_theta)
    k = rope((h @ a["wk"]).reshape(T, -1, Dh), cfg.rope_theta)
    v = (h @ a["wv"]).reshape(T, -1, Dh)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * cfg.attn_scale
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v).reshape(T, -1)
    h = rmsnorm(o @ a["wo"], sp["ln_ff"], eps)
    mlp, ad = sp["mlp"], hp["adapter"]
    gate = h @ mlp["w_gate"] + (h @ ad["a"]) @ ad["b_gate"]
    up = h @ mlp["w_up"] + (h @ ad["a"]) @ ad["b_up"]
    y = (jax.nn.gelu(gate, approximate=False) * up) @ mlp["w_down"]
    return y @ hp["linear"]


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def logits(params: Dict[str, Any], tokens, cfg):
    """tokens: (T,) -> (T, vocab)."""
    x = emb = params["embed"]["tok"][tokens]
    hybrid = {h: i for i, h in enumerate(cfg.hybrid_layer_ids)}
    for layer in range(cfg.num_layers):
        extra = None
        if layer in hybrid:
            i = hybrid[layer]
            extra = shared_block(
                _at(params["shared"], i % cfg.num_mem_blocks),
                _at(params["hybrid"], i), x, emb, cfg)
        x = mamba_layer(_at(params["blocks"], layer), x, cfg, extra)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return x @ params["embed"]["tok"].T


def loss(params, batch, cfg):
    """Mean next-token cross entropy over the positions with loss_mask 1."""
    total, count = 0.0, 0.0
    for r in range(batch["tokens"].shape[0]):
        lg = logits(params, batch["tokens"][r], cfg)
        nll = (jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, batch["labels"][r][:, None], -1)[:, 0])
        total = total + jnp.sum(nll * batch["loss_mask"][r])
        count = count + jnp.sum(batch["loss_mask"][r])
    return total / jnp.maximum(count, 1.0)


def loss_and_grads(params, batch, cfg):
    """Loss and gradients of float32 ``params``."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, batch, cfg)
