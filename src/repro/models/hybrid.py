"""Zamba2 hybrid (arXiv:2411.15242): a stack of Mamba2 layers, some of
which (``cfg.hybrid_layer_ids``) are "hybrid".  Before its Mamba2 layer,
the i-th hybrid layer runs shared transformer block ``i % num_mem_blocks``
over the concatenation of the hidden states and the original token
embeddings (width ``attn_input_width``), then its own ``linear`` map, and
adds the result to the Mamba2 layer's input before that layer's norm (not
to its residual).

Shared block, as in Zamba2's ``Zamba2AttentionDecoderLayer``:
``input_layernorm`` -> attention (``attn_input_width`` -> d_model, heads of
``head_dim``, rope, softmax scale ``attn_scale``) -> ``pre_ff_layernorm``
-> gated MLP whose gate and up projections get the hybrid layer's own
low-rank adapter (rank ``adapter_rank``).  No residual inside the block.

Training and prefill/decode run the same layers; serving keeps one KV
cache per hybrid layer beside the Mamba2 states.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models.params import pdef
from repro.sharding import constrain

Params = Dict[str, Any]


def param_defs(cfg: ModelConfig) -> Params:
    nb, n_app, d = cfg.num_mem_blocks, len(cfg.hybrid_layer_ids), cfg.d_model
    hybrid: Params = {
        "linear": pdef((n_app, d, d), ("layers", "embed", None),
                       init="scaled"),
    }
    if cfg.adapter_rank:
        hybrid["adapter"] = L.lora_defs(cfg, n_app)
    return {
        "embed": L.embed_defs(cfg),
        "blocks": M.block_defs(cfg, cfg.num_layers),
        "shared": {
            "ln_in": L.norm_defs(nb, cfg.attn_in_dim),
            "attn": L.attention_defs(cfg, nb),
            "ln_ff": L.norm_defs(nb, d),
            "mlp": L.mlp_defs(cfg, nb),
        },
        "hybrid": hybrid,
        "ln_f": L.norm_defs(0, d),
    }


def _take(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _shared_block(sp: Params, hp: Params, cfg: ModelConfig, run: RunConfig,
                  x, emb, positions, cache, cache_pos, kv_len):
    """One hybrid layer's shared block and linear map: its contribution to
    the Mamba2 layer's input, and the updated KV cache."""
    with jax.named_scope("shared_block"):
        h = jnp.concatenate([x, emb], axis=-1)
        h = L.rmsnorm(sp["ln_in"], h, cfg, run)
        h, new_cache = L.attention(sp["attn"], cfg, run, h,
                                   positions=positions, cache=cache,
                                   cache_pos=cache_pos, kv_len=kv_len)
        h = L.rmsnorm(sp["ln_ff"], h, cfg, run)
        h = L.mlp(sp["mlp"], cfg, run, h, lora=hp.get("adapter"))
        out = constrain(h @ hp["linear"], "batch", None, None)
    return out, new_cache


def _run(params, cfg, run, x, positions, mamba_state=None, kv_cache=None,
         cache_pos=None, kv_len=None):
    """Every layer in order: runs of plain Mamba2 layers (scanned) and the
    hybrid layers between them."""
    emb = x
    blocks = params["blocks"]
    shared = lambda sp, hp, *a: _shared_block(sp, hp, cfg, run, *a)
    if run.remat != "none":
        shared = jax.checkpoint(shared)

    def layers(x, lo, hi, extra=None):
        st = (None if mamba_state is None
              else jax.tree.map(lambda a: a[lo:hi], mamba_state))
        return M.run_layers(jax.tree.map(lambda a: a[lo:hi], blocks), cfg,
                            run, x, st, extra)

    new_states, new_kv, lo = [], [], 0
    for i, h_id in enumerate(cfg.hybrid_layer_ids):
        if h_id > lo:
            x, ns = layers(x, lo, h_id)
            new_states.append(ns)
        c_i = None if kv_cache is None else _take(kv_cache, i)
        t, nc = shared(_take(params["shared"], i % cfg.num_mem_blocks),
                       _take(params["hybrid"], i), x, emb, positions, c_i,
                       cache_pos, kv_len)
        new_kv.append(nc)
        x, ns = layers(x, h_id, h_id + 1, extra=t)
        new_states.append(ns)
        lo = h_id + 1
    if cfg.num_layers > lo:
        x, ns = layers(x, lo, cfg.num_layers)
        new_states.append(ns)

    out_state = (None if mamba_state is None else
                 jax.tree.map(lambda *xs: jnp.concatenate(xs), *new_states))
    out_kv = (None if kv_cache is None else
              jax.tree.map(lambda *xs: jnp.stack(xs), *new_kv))
    return L.rmsnorm(params["ln_f"], x, cfg, run), out_state, out_kv


def forward(params, cfg, run, batch):
    x = L.embed(params["embed"], batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x, _, _ = _run(params, cfg, run, x, positions)
    return x


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    return {
        "mamba": M.state_defs(cfg, cfg.num_layers, batch),
        "kv": L.kv_cache_defs(cfg, len(cfg.hybrid_layer_ids), batch,
                              max_len),
    }


def prefill(params, cfg, run, batch, cache):
    x = L.embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    positions = jnp.arange(S)
    x, ms, kv = _run(params, cfg, run, x, positions,
                     mamba_state=cache["mamba"], kv_cache=cache["kv"],
                     cache_pos=0, kv_len=S)
    logits = L.logits_out(params["embed"], cfg, run, x[:, -1:])
    return logits, {"mamba": ms, "kv": kv}


def decode(params, cfg, run, tokens, cache, pos):
    x = L.embed(params["embed"], tokens)
    positions = pos + jnp.arange(1)
    x, ms, kv = _run(params, cfg, run, x, positions,
                     mamba_state=cache["mamba"], kv_cache=cache["kv"],
                     cache_pos=pos, kv_len=pos + 1)
    logits = L.logits_out(params["embed"], cfg, run, x)
    return logits, {"mamba": ms, "kv": kv}
