"""Mamba2 (SSD — state-space duality) language model, pure JAX.

Per-block structure (arXiv:2405.21060):
  in projections (z, x, B, C, dt)  ->  causal depthwise conv on (x, B, C)
  -> SSD scan  ->  gated RMSNorm  ->  out projection.

Projections are SPLIT (not fused) so every sharded feature dim divides the
model axis cleanly (the fused mamba2 in_proj dim 2*d_in+2GN+H rarely
divides 16).  SSD head dim shards on the model axis iff divisible
(zamba2: 64 heads -> sharded; mamba2-130m: 24 heads -> replicated inner
scan, projections still sharded).

B/C come in ``cfg.ssm_groups`` groups (Mamba2's ``ngroups``): head h reads
group h // (H / G), and the gated RMSNorm normalises each group of
d_inner / G channels on its own (Mamba2's ``RMSNormGated``).

Decode state is O(1): conv tails (W-1 tokens) + SSM state (H, P, N).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RunConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models.params import pdef
from repro.sharding import constrain

Params = Dict[str, Any]


def block_defs(cfg: ModelConfig, n: int) -> Params:
    d, din = cfg.d_model, cfg.ssm_inner
    N, H, W, G = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv, cfg.ssm_groups
    lead, ll = ((n,), ("layers",)) if n else ((), ())
    return {
        "ln": L.norm_defs(n, d),
        "w_z": pdef(lead + (d, din), ll + ("embed", "ffn"), init="scaled"),
        "w_x": pdef(lead + (d, din), ll + ("embed", "ffn"), init="scaled"),
        "w_B": pdef(lead + (d, G * N), ll + ("embed", None), init="scaled"),
        "w_C": pdef(lead + (d, G * N), ll + ("embed", None), init="scaled"),
        "w_dt": pdef(lead + (d, H), ll + ("embed", None), init="scaled"),
        "conv_x": pdef(lead + (W, din), ll + (None, "ffn"), init="scaled"),
        "conv_B": pdef(lead + (W, G * N), ll + (None, None), init="scaled"),
        "conv_C": pdef(lead + (W, G * N), ll + (None, None), init="scaled"),
        "conv_x_b": pdef(lead + (din,), ll + ("ffn",), init="zeros"),
        "conv_B_b": pdef(lead + (G * N,), ll + (None,), init="zeros"),
        "conv_C_b": pdef(lead + (G * N,), ll + (None,), init="zeros"),
        "A_log": pdef(lead + (H,), ll + (None,), init="ssm_a",
                      dtype=jnp.float32),
        "D": pdef(lead + (H,), ll + (None,), init="ones", dtype=jnp.float32),
        "dt_bias": pdef(lead + (H,), ll + (None,), init="ssm_dt",
                        dtype=jnp.float32),
        "norm": pdef(lead + (din,), ll + ("ffn",), init="ones"),
        "w_out": pdef(lead + (din, d), ll + ("ffn", "embed"), init="scaled"),
    }


def param_defs(cfg: ModelConfig) -> Params:
    return {
        "embed": L.embed_defs(cfg),
        "blocks": block_defs(cfg, cfg.num_layers),
        "ln_f": L.norm_defs(0, cfg.d_model),
    }


@ops.scoped("conv")
def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 tail: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: (B, S, C); w: (W, C); returns (y, new_tail).

    tail: (B, W-1, C) previous context (decode) or None (train: zero pad).
    """
    W = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    y = lax.conv_general_dilated(
        xp, w[:, None, :],
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x.shape[2])
    new_tail = xp[:, -(W - 1):] if W > 1 else tail
    return jax.nn.silu(y + b), new_tail


def block_fwd(p: Params, cfg: ModelConfig, run: RunConfig, x: jax.Array,
              state: Optional[Params] = None,
              extra: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Optional[Params]]:
    """x: (B, S, d). state (decode): conv tails + ssm state; None for train.
    extra: added to the layer's input before its norm, not to the residual
    (a Zamba2 hybrid layer's shared-block output)."""
    Bb, S, _ = x.shape
    N, H, P, G = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups
    h = L.rmsnorm(p["ln"], x if extra is None else x + extra, cfg, run)

    with jax.named_scope("proj"):
        z = constrain(h @ p["w_z"], "batch", None, "ffn")
        xs = constrain(h @ p["w_x"], "batch", None, "ffn")
        Bm = h @ p["w_B"]
        Cm = h @ p["w_C"]
        dt = h @ p["w_dt"]

    tails = (None, None, None) if state is None else (
        state["tail_x"], state["tail_B"], state["tail_C"])
    xs, tx = _causal_conv(xs, p["conv_x"], p["conv_x_b"], tails[0])
    Bm, tb = _causal_conv(Bm, p["conv_B"], p["conv_B_b"], tails[1])
    Cm, tc = _causal_conv(Cm, p["conv_C"], p["conv_C_b"], tails[2])

    # shard SSD heads on the model axis when they divide (zamba2: 64H);
    # otherwise shard the head_dim P (mamba2-130m: 24H, P=64) — the rules
    # dedup makes the two tags exclusive.
    xh = constrain(xs.reshape(Bb, S, H, P),
                   "batch", None, "heads_ssm", "ssm_p")
    Bg = Bm.reshape(Bb, S, G, N)
    Cg = Cm.reshape(Bb, S, G, N)
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    init = None if state is None else state["ssm"]
    if S == 1 and state is not None:
        # decode: O(1) single-token recurrence — no chunk padding
        y1, new_ssm = ops.ssd_decode(
            xh[:, 0], dtp[:, 0], A, Bg[:, 0], Cg[:, 0], init)
        y = y1[:, None]
    else:
        y, new_ssm = ops.ssd(xh, dtp, A, Bg, Cg, chunk=cfg.ssm_chunk,
                             init_state=init, return_state=True,
                             use_pallas=run.use_pallas)
    with jax.named_scope("ssd"):
        y = y + (xh.astype(jnp.float32)
                 * p["D"][None, None, :, None]).astype(y.dtype)
    y = constrain(y, "batch", None, "heads_ssm", "ssm_p")
    y = y.reshape(Bb, S, H * P)

    with jax.named_scope("norm"):
        y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    w_norm = p["norm"]
    if G > 1:
        y, w_norm = y.reshape(Bb, S, G, -1), w_norm.reshape(G, -1)
    y = ops.rmsnorm(y, w_norm, eps=cfg.norm_eps, use_pallas=run.use_pallas)
    y = y.reshape(Bb, S, H * P)
    with jax.named_scope("proj"):
        out = constrain(y @ p["w_out"], "batch", None, None)
    new_state = None
    if state is not None:
        new_state = {"tail_x": tx, "tail_B": tb, "tail_C": tc,
                     "ssm": new_ssm.astype(state["ssm"].dtype)}
    return x + out, new_state


def state_defs(cfg: ModelConfig, n: int, batch: int) -> Params:
    """Decode-state ParamDefs for n stacked mamba blocks."""
    N, H, P, W = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv
    din, G = cfg.ssm_inner, cfg.ssm_groups
    lead, ll = ((n,), ("layers",)) if n else ((), ())
    return {
        "tail_x": pdef(lead + (batch, W - 1, din),
                       ll + ("batch", None, "ffn"), init="zeros"),
        "tail_B": pdef(lead + (batch, W - 1, G * N),
                       ll + ("batch", None, None), init="zeros"),
        "tail_C": pdef(lead + (batch, W - 1, G * N),
                       ll + ("batch", None, None), init="zeros"),
        "ssm": pdef(lead + (batch, H, P, N),
                    ll + ("batch", "heads_ssm", "ssm_p", None), init="zeros",
                    dtype=jnp.float32),
    }


def run_layers(blocks, cfg, run, x, state=None, extra=None):
    """The stacked layers of ``blocks`` in order (scanned, or unrolled);
    ``extra`` as in ``block_fwd``, for every layer.  Returns the hidden
    states and the stacked new states (None without ``state``)."""
    fn = lambda p, hh, ss, ex: block_fwd(p, cfg, run, hh, ss, ex)
    if run.remat != "none":
        fn = jax.checkpoint(fn)

    def body(carry, xs_):
        p_l, s_l = xs_
        return fn(p_l, carry, s_l, extra)

    if run.scan_layers:
        return lax.scan(body, x, (blocks, state))
    outs = []
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        p_l = jax.tree.map(lambda a: a[i], blocks)
        s_l = None if state is None else jax.tree.map(lambda a: a[i], state)
        x, ns = fn(p_l, x, s_l, extra)
        outs.append(ns)
    return x, (None if state is None
               else jax.tree.map(lambda *s: jnp.stack(s), *outs))


def _run_blocks(params, cfg, run, x, state=None):
    x, new_state = run_layers(params["blocks"], cfg, run, x, state)
    return L.rmsnorm(params["ln_f"], x, cfg, run), new_state


def forward(params, cfg, run, batch):
    x = L.embed(params["embed"], batch["tokens"])
    x, _ = _run_blocks(params, cfg, run, x)
    return x


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    return state_defs(cfg, cfg.num_layers, batch)


def prefill(params, cfg, run, batch, cache):
    x = L.embed(params["embed"], batch["tokens"])
    x, cache = _run_blocks(params, cfg, run, x, state=cache)
    logits = L.logits_out(params["embed"], cfg, run, x[:, -1:])
    return logits, cache


def decode(params, cfg, run, tokens, cache, pos):
    x = L.embed(params["embed"], tokens)
    x, cache = _run_blocks(params, cfg, run, x, state=cache)
    logits = L.logits_out(params["embed"], cfg, run, x)
    return logits, cache
