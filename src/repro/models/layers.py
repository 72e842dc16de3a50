"""Shared neural-net layers for the model zoo (pure JAX, no flax).

Conventions:
  * params are nested dicts of jnp arrays, built from ParamDef trees;
  * every forward fn takes (p, cfg, run, ...) where p is the param subtree;
  * activations carry logical sharding constraints via sharding.constrain;
  * attention dispatches between a heads-sharded flash path and a
    kv-materialized q-chunked path for archs whose head count does not
    divide the model axis (qwen1.5-32b 40H, qwen1.5-4b 20H, whisper 6H).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RunConfig
from repro.kernels import ops
from repro.kernels.ref import NEG_INF
from repro.models.params import pdef
from repro.sharding import constrain, current_rules

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def norm_defs(L: int, d: int):
    return pdef((L, d) if L else (d,),
                ("layers", None) if L else (None,), init="ones")


def attention_defs(cfg: ModelConfig, L: int, *, cross: bool = False):
    """Q/K/V read ``cfg.attn_in_dim`` features; the output is d_model."""
    d, din, qd, kvd = cfg.d_model, cfg.attn_in_dim, cfg.q_dim, cfg.kv_dim
    lead = (L,) if L else ()
    ll = ("layers",) if L else ()
    out: Params = {
        "wq": pdef(lead + (din, qd), ll + ("embed", "qkv"), init="scaled"),
        "wk": pdef(lead + (din, kvd), ll + ("embed", "qkv"), init="scaled"),
        "wv": pdef(lead + (din, kvd), ll + ("embed", "qkv"), init="scaled"),
        "wo": pdef(lead + (qd, d), ll + ("qkv", "embed"), init="scaled"),
    }
    if cfg.qkv_bias:
        out["bq"] = pdef(lead + (qd,), ll + ("qkv",), init="zeros")
        out["bk"] = pdef(lead + (kvd,), ll + ("qkv",), init="zeros")
        out["bv"] = pdef(lead + (kvd,), ll + ("qkv",), init="zeros")
    return out


def mlp_defs(cfg: ModelConfig, L: int, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    lead = (L,) if L else ()
    ll = ("layers",) if L else ()
    out: Params = {
        "w_up": pdef(lead + (d, f), ll + ("embed", "ffn"), init="scaled"),
        "w_down": pdef(lead + (f, d), ll + ("ffn", "embed"), init="scaled"),
    }
    if cfg.gated_mlp:
        out["w_gate"] = pdef(lead + (d, f), ll + ("embed", "ffn"),
                             init="scaled")
    if cfg.mlp_bias:
        out["b_up"] = pdef(lead + (f,), ll + ("ffn",), init="zeros")
        out["b_down"] = pdef(lead + (d,), ll + (None,), init="zeros")
    return out


def lora_defs(cfg: ModelConfig, L: int):
    """Low-rank adapters of a gated MLP's gate and up projections, one per
    layer: ``x @ a`` (rank ``cfg.adapter_rank``) times ``b_gate`` and
    ``b_up``."""
    d, r, f = cfg.d_model, cfg.adapter_rank, cfg.d_ff
    return {
        "a": pdef((L, d, r), ("layers", "embed", None), init="scaled"),
        "b_gate": pdef((L, r, f), ("layers", "lora", "ffn"), init="scaled"),
        "b_up": pdef((L, r, f), ("layers", "lora", "ffn"), init="scaled"),
    }


def moe_defs(cfg: ModelConfig, L: int):
    """Expert weights carry BOTH "expert" and "ffn" logical tags; the
    rules dedup shards on whichever divides: qwen3-moe (128e) -> EP on the
    expert dim, mixtral (8e < 16) -> TP on the per-expert ffn dim."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": pdef((L, d, E), ("layers", "embed", None),
                       init="scaled", dtype=jnp.float32),
        "w_gate": pdef((L, E, d, f), ("layers", "expert", "embed", "ffn"),
                       init="scaled"),
        "w_up": pdef((L, E, d, f), ("layers", "expert", "embed", "ffn"),
                     init="scaled"),
        "w_down": pdef((L, E, f, d), ("layers", "expert", "ffn", "embed"),
                       init="scaled"),
    }


# ---------------------------------------------------------------------------
# Norm / activations / RoPE
# ---------------------------------------------------------------------------


def rmsnorm(p, x, cfg: ModelConfig, run: RunConfig):
    return ops.rmsnorm(x, p, eps=cfg.norm_eps, use_pallas=run.use_pallas)


_ACTS = {
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "gelu_tanh": functools.partial(jax.nn.gelu, approximate=True),
}


def act_fn(name: str):
    """``gelu`` is the exact (erf) GELU; ``gelu_tanh`` its tanh
    approximation (``gelu_pytorch_tanh``)."""
    return _ACTS[name]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: (S,) or broadcastable."""
    if theta <= 0:
        return x
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """positions: (S,) (possibly traced). Returns (S, d)."""
    pos = positions.astype(jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# KV cache (bf16 or int8-quantized)
# ---------------------------------------------------------------------------


def kv_cache_defs(cfg: ModelConfig, L: int, batch: int, max_len: int):
    """Abstract structure for one stack of per-layer KV caches."""
    Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
    shp = (L, batch, max_len, Hkv, Dh)
    logical = ("layers", "batch", "kv_seq", "heads", None)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": pdef(shp, logical, init="zeros", dtype=jnp.int8),
            "v": pdef(shp, logical, init="zeros", dtype=jnp.int8),
            "k_scale": pdef(shp[:-1], logical[:-1], init="zeros",
                            dtype=jnp.float32),
            "v_scale": pdef(shp[:-1], logical[:-1], init="zeros",
                            dtype=jnp.float32),
        }
    return {
        "k": pdef(shp, logical, init="zeros", dtype=jnp.bfloat16),
        "v": pdef(shp, logical, init="zeros", dtype=jnp.bfloat16),
    }


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 quantization. x: (..., Dh)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_update(cache: Params, layer_k: jax.Array, layer_v: jax.Array,
                 pos, cfg: ModelConfig) -> Params:
    """Write new K/V (B, S_new, Hkv, Dh) into a single-layer cache at pos."""
    out = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        qk, sk = quantize_kv(layer_k)
        qv, sv = quantize_kv(layer_v)
        out["k"] = lax.dynamic_update_slice_in_dim(cache["k"], qk, pos, 1)
        out["v"] = lax.dynamic_update_slice_in_dim(cache["v"], qv, pos, 1)
        out["k_scale"] = lax.dynamic_update_slice_in_dim(
            cache["k_scale"], sk, pos, 1)
        out["v_scale"] = lax.dynamic_update_slice_in_dim(
            cache["v_scale"], sv, pos, 1)
    else:
        out["k"] = lax.dynamic_update_slice_in_dim(
            cache["k"], layer_k.astype(cache["k"].dtype), pos, 1)
        out["v"] = lax.dynamic_update_slice_in_dim(
            cache["v"], layer_v.astype(cache["v"].dtype), pos, 1)
    return out


def cache_read(cache: Params, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    if cfg.kv_cache_dtype == "int8":
        return (dequantize_kv(cache["k"], cache["k_scale"]),
                dequantize_kv(cache["v"], cache["v_scale"]))
    return cache["k"], cache["v"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _heads_shardable(n_heads: int) -> bool:
    r = current_rules()
    if r is None:
        return True
    return r.resolve_dim("heads", n_heads) is not None


def _attention_kvseq(q, k, v, *, causal, q_offset, kv_len, sliding_window,
                     block_q: int = 1024, scale=None):
    """Fallback attention for non-divisible head counts: KV sequence is
    sharded on the model axis; scores materialize per q-chunk and the
    softmax reduction crosses shards (flash-decoding layout).
    """
    B, Sq, Hq, Dh = q.shape
    Sk = k.shape[1]
    G = Hq // max(k.shape[2], 1)
    scale = scale if scale is not None else Dh ** -0.5
    k = constrain(k, "batch", "kv_seq", None, None)
    v = constrain(v, "batch", "kv_seq", None, None)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(Sk)
    valid = Sk if kv_len is None else kv_len

    block_q = min(block_q, Sq)
    pad_q = (-Sq) % block_q
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    nq = qp.shape[1] // block_q
    qb = qp.reshape(B, nq, block_q, Hq, Dh).transpose(1, 0, 2, 3, 4)

    def one_block(args):
        qblk, i = args
        qf = (qblk.astype(jnp.float32) * scale).reshape(
            B, block_q, k.shape[2], G, Dh)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kf)
        s = constrain(s, "batch", None, None, None, "kv_seq")
        q_pos = q_offset + i * block_q + jnp.arange(block_q)
        mask = k_pos[None, :] < valid
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if sliding_window:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bqhgk,bkhd->bqhgd", p, vf)
        return o.reshape(B, block_q, Hq, Dh)

    if nq == 1:
        out = one_block((qb[0], 0))[None]
    else:
        out = lax.map(one_block, (qb, jnp.arange(nq)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, -1, Hq, Dh)[:, :Sq]
    return out.astype(q.dtype)


@ops.scoped("attention")
def attention(p: Params, cfg: ModelConfig, run: RunConfig, x: jax.Array,
              *, positions: jax.Array, causal: bool = True,
              cache: Optional[Params] = None, cache_pos=None,
              kv_len=None, xkv: Optional[jax.Array] = None,
              cache_read_only: bool = False,
              use_rope: bool = True) -> Tuple[jax.Array, Optional[Params]]:
    """General GQA attention with optional KV cache and cross-attention.

    x: (B, S, cfg.attn_in_dim). xkv: encoder output for cross-attention.
    cache: single-layer cache dict (already sliced out of the stack).
    cache_pos: scalar write offset into the cache.
    cache_read_only: cross-attention decode — use cached K/V, no update.
    Returns (out, updated_cache).
    """
    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = cfg.attn_scale or None
    src = x if xkv is None else xkv

    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = constrain(q, "batch", None, "qkv")
    q = q.reshape(B, S, Hq, Dh)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)

    if cache_read_only:
        # cross-attention during decode: KV precomputed at prefill
        k, v = cache_read(cache, cfg)
        new_cache = cache
    else:
        k = src @ p["wk"]
        v = src @ p["wv"]
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = constrain(k, "batch", None, "qkv").reshape(B, -1, Hkv, Dh)
        v = constrain(v, "batch", None, "qkv").reshape(B, -1, Hkv, Dh)
        if use_rope and xkv is None:
            k = rope(k, positions, cfg.rope_theta)
        new_cache = cache
        if cache is not None:
            new_cache = cache_update(cache, k, v, cache_pos, cfg)
            k, v = cache_read(new_cache, cfg)

    q_offset = positions[0] if positions.ndim else positions
    heads_ok = _heads_shardable(Hq)
    if S == 1:
        # decode: flash-decoding layout — KV sequence sharded on the model
        # axis, partial softmax reduced across shards by GSPMD.
        out = _attention_kvseq(
            q, k, v, causal=causal, q_offset=q_offset,
            kv_len=kv_len, sliding_window=cfg.sliding_window, scale=scale)
    elif heads_ok:
        # TP over heads. For GQA, K/V are repeated up to Hq *after* the
        # head constraint so every intermediate carries a clean 16-way
        # head sharding (the grouped (Hkv, G) layout cannot express a
        # single mesh axis and triggers involuntary SPMD remats).
        q = constrain(q, "batch", None, "heads", None)
        if k.shape[2] != Hq:
            G = Hq // k.shape[2]
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        k = constrain(k, "batch", None, "heads", None)
        v = constrain(v, "batch", None, "heads", None)
        out = ops.flash_attention(
            q, k, v, causal=causal, q_offset=q_offset,
            kv_len=kv_len, sliding_window=cfg.sliding_window,
            block_k=run.attn_block_k, scale=scale, use_pallas=run.use_pallas,
            custom_vjp=run.flash_custom_vjp,
            carry_constrain=lambda t: constrain(
                t, *(("batch", None, "heads") + (None,) * (t.ndim - 3))))
    else:
        # head count does not divide the model axis (qwen1.5-32b 40H,
        # qwen1.5-4b 20H, whisper 6H): shard the QUERY sequence instead
        # (sequence-parallel attention); K/V replicated per layer.
        q = constrain(q, "batch", "q_seq", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
        out = ops.flash_attention(
            q, k, v, causal=causal, q_offset=q_offset,
            kv_len=kv_len, sliding_window=cfg.sliding_window,
            block_k=run.attn_block_k, scale=scale, use_pallas=run.use_pallas,
            custom_vjp=run.flash_custom_vjp,
            carry_constrain=lambda t: constrain(
                t, *(("batch", "q_seq") + (None,) * (t.ndim - 2))))

    out = out.reshape(B, S, Hq * Dh)
    out = constrain(out, "batch", None, "qkv")
    y = out @ p["wo"]
    return constrain(y, "batch", None, None), new_cache


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------


@ops.scoped("mlp")
def mlp(p: Params, cfg: ModelConfig, run: RunConfig, x: jax.Array,
        act: Optional[str] = None,
        lora: Optional[Params] = None) -> jax.Array:
    """``lora``: one layer's ``lora_defs`` leaves, added to the gate and up
    projections of a gated MLP."""
    a = act_fn(act or cfg.act)
    up = x @ p["w_up"]
    if "b_up" in p:
        up = up + p["b_up"]
    gate = x @ p["w_gate"] if "w_gate" in p else None
    if lora is not None:
        with jax.named_scope("adapter"):
            r = x @ lora["a"]
            gate = gate + r @ lora["b_gate"]
            up = up + r @ lora["b_up"]
    up = constrain(up, "batch", None, "ffn")
    if gate is not None:
        h = a(constrain(gate, "batch", None, "ffn")) * up
    else:
        h = a(up)
    y = h @ p["w_down"]
    if "b_down" in p:
        y = y + p["b_down"]
    return constrain(y, "batch", None, None)


@ops.scoped("mlp")
def moe_block(p: Params, cfg: ModelConfig, run: RunConfig,
              x: jax.Array) -> jax.Array:
    """Top-k MoE dispatch. Two implementations:

    shardmap (default, §Perf winner): explicit expert parallelism.  The
      batch is sharded over (pod, data) and replicated over model, so each
      model column already holds every token — no all-to-all is needed.
      Each device routes its local tokens, runs ONLY its local experts
      (qwen3: 8/128 experts; mixtral: all 8 experts on a 1/16 ffn slice),
      and one psum over the model axis combines the (disjoint or
      f-partial) contributions.  Collectives: exactly one psum of the
      activation per layer.

    gspmd (baseline): per-row sort-based dispatch under vmap, sharding
      left to the compiler — measured to produce TB-scale all-reduce /
      all-to-all chatter from the scatter/gather ops (EXPERIMENTS.md
      §Perf iterations 1-2).
    """
    r = current_rules()
    if (run.moe_impl == "shardmap" and r is not None
            and "model" in r.mesh.shape and x.shape[1] > 1):
        # decode (S=1) stays on the gspmd path: with ~8 local tokens the
        # shard_map dispatch overhead is unamortized (§Perf, measured
        # +13% on qwen3/mixtral decode_32k).
        return _moe_block_shardmap(p, cfg, run, x)
    return _moe_block_gspmd(p, cfg, run, x)


def _moe_block_gspmd(p: Params, cfg: ModelConfig, run: RunConfig,
                     x: jax.Array) -> jax.Array:
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = max(int(math.ceil(S * K / E * cfg.moe_capacity_factor)), 1)
    a = act_fn(cfg.act)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(gates, K)  # (B, S, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # NOTE (§Perf iter 1, kept for the record): constraining the expert
    # weights d-replicated here kills the TB-scale activation all-reduces
    # but makes GSPMD drop its d-contraction compute split (9x flops) and
    # regresses decode. Net-negative -> reverted; train/prefill use the
    # shard_map path instead.
    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]

    def route_row(xr, er, wr):
        # xr: (S, d), er/wr: (S, K)
        flat_e = er.reshape(-1)                       # (S*K,)
        order = jnp.argsort(flat_e, stable=True)
        tok = order // K                              # source token
        se = flat_e[order]
        start = jnp.searchsorted(se, jnp.arange(E))   # first slot per expert
        pos = jnp.arange(S * K) - start[se]
        keep = pos < C
        slot = jnp.clip(se * C + pos, 0, E * C - 1)
        xe = jnp.zeros((E * C, d), x.dtype)
        xe = xe.at[slot].add(jnp.where(keep[:, None], xr[tok], 0))
        xe = xe.reshape(E, C, d)
        # expert FFN — sharding propagates from the weights: EP on the
        # expert dim (qwen3-moe) or TP on the per-expert ffn dim (mixtral);
        # see moe_defs. (No explicit constraint: this code runs under vmap.)
        g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xe, w_up)
        h = a(g) * u
        ye = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E * C, d)
        # combine
        contrib = ye[slot] * jnp.where(keep, wr.reshape(-1)[order], 0.0
                                       )[:, None].astype(ye.dtype)
        y = jnp.zeros((S, d), ye.dtype).at[tok].add(contrib)
        return y

    y = jax.vmap(route_row)(x, top_e, top_w)
    return constrain(y.astype(x.dtype), "batch", None, None)


def _moe_block_shardmap(p: Params, cfg: ModelConfig, run: RunConfig,
                        x: jax.Array) -> jax.Array:
    from jax.sharding import PartitionSpec as PS

    r = current_rules()
    mesh = r.mesh
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    d, f = cfg.d_model, cfg.d_ff
    a = act_fn(cfg.act)
    n_model = mesh.shape.get("model", 1)
    e_sharded = E % n_model == 0 and n_model > 1
    E_loc = E // n_model if e_sharded else E

    x_spec = r.spec(("batch", None, None), x.shape)
    if e_sharded:
        w_in_spec = PS("model", None, None)       # (E_loc, d, f) local
        w_out_spec = PS("model", None, None)      # (E_loc, f, d) local
    else:
        w_in_spec = PS(None, None, "model")       # (E, d, f_loc) local
        w_out_spec = PS(None, "model", None)      # (E, f_loc, d) local

    def local_moe(xl, router, wg, wu, wd):
        B_l, S, _ = xl.shape
        T = B_l * S
        C = max(int(math.ceil(T * K / E * cfg.moe_capacity_factor)), 1)
        xt = xl.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router          # (T, E)
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = lax.top_k(gates, K)                # (T, K)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        flat_e = top_e.reshape(-1)                        # (T*K,) global ids
        order = jnp.argsort(flat_e, stable=True)
        tok = order // K
        se = flat_e[order]
        base = (lax.axis_index("model") * E_loc) if e_sharded else 0
        le = se - base                                    # local expert id
        local = (le >= 0) & (le < E_loc)
        start = jnp.searchsorted(se, base + jnp.arange(E_loc))
        pos = jnp.arange(T * K) - start[jnp.clip(le, 0, E_loc - 1)]
        keep = local & (pos < C)
        slot = jnp.clip(le * C + pos, 0, E_loc * C - 1)

        xe = jnp.zeros((E_loc * C, d), xt.dtype)
        xe = xe.at[slot].add(jnp.where(keep[:, None], xt[tok], 0))
        xe = xe.reshape(E_loc, C, d)
        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", a(g) * u, wd).reshape(E_loc * C, d)

        wsel = jnp.where(keep, top_w.reshape(-1)[order], 0.0)
        contrib = ye[slot] * wsel[:, None].astype(ye.dtype)
        y = jnp.zeros((T, d), ye.dtype).at[tok].add(contrib)
        # disjoint expert contributions (EP) or f-slice partials (TP):
        # one psum over the model axis combines either way.
        y = lax.psum(y, "model")
        return y.reshape(B_l, S, d).astype(xl.dtype)

    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, PS(None, None), w_in_spec, w_in_spec, w_out_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, p["router"].astype(jnp.float32), p["w_gate"], p["w_up"],
              p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_defs(cfg: ModelConfig):
    out = {"tok": pdef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        out["lm_head"] = pdef((cfg.vocab_size, cfg.d_model),
                              ("vocab", "embed"), init="scaled")
    return out


@ops.scoped("embed")
def embed(p: Params, tokens: jax.Array) -> jax.Array:
    y = jnp.take(p["tok"], tokens, axis=0)
    return constrain(y, "batch", None, None)


def lm_head_weight(p: Params, cfg: ModelConfig) -> jax.Array:
    return p["tok"] if cfg.tie_embeddings else p["lm_head"]


def logits_out(p: Params, cfg: ModelConfig, run: RunConfig,
               x: jax.Array) -> jax.Array:
    w = lm_head_weight(p, cfg)
    y = jnp.einsum("bsd,vd->bsv", x, w)
    if run.logits_in_fp32:
        y = y.astype(jnp.float32)
    return constrain(y, "batch", None, "vocab")
