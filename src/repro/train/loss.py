"""LM loss head.

``ce_blockwise`` is a custom-VJP vocab-blockwise cross entropy: neither the
forward nor the backward pass ever materializes the (T, V) logit matrix —
forward keeps online (max, logsumexp, target-logit) statistics per vocab
block; backward recomputes each block's logits and immediately contracts
them into (d_hidden, d_w) contributions.  At qwen scale
(1M tokens x 152k vocab) direct CE residuals are ~0.6 PB; blockwise is
O(T*D + V*D) — this is what lets the 94-layer MoE train_4k cell fit.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RunConfig
from repro.kernels.ref import NEG_INF, _pad_to
from repro.models import layers as L
from repro.models import registry
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# Blockwise CE with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ce_blockwise(hidden, w_vocab, targets, valid, block_v: int = 8192,
                 ce_dtype=jnp.bfloat16):
    """Mean NLL over valid positions. hidden: (T, D); w_vocab: (V, D).

    The per-block logits matmul runs with ``ce_dtype`` inputs and f32
    accumulation (§Perf: halves the 19x whole-hidden reads at qwen vocab)."""
    nll, _ = _ce_fwd_stats(hidden, w_vocab, targets, block_v, ce_dtype)
    return _masked_mean(nll, valid)


def _block_logits(h, w_blk, ce_dtype):
    return lax.dot_general(
        h.astype(ce_dtype), w_blk.astype(ce_dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _masked_mean(nll, valid):
    if valid is not None:
        nll = nll * valid
        return nll.sum() / jnp.maximum(valid.sum(), 1.0)
    return nll.mean()


def _ce_fwd_stats(hidden, w_vocab, targets, block_v,
                  ce_dtype=jnp.bfloat16):
    T, D = hidden.shape
    V = w_vocab.shape[0]
    block_v = min(block_v, V)
    wp, _ = _pad_to(w_vocab, 0, block_v)
    nb = wp.shape[0] // block_v
    hf = hidden
    wb = wp.reshape(nb, block_v, D)

    def body(carry, blk):
        m, l, tgt = carry
        w_blk, j = blk
        logits = _block_logits(hf, w_blk, ce_dtype)  # (T, block_v) f32
        logits = constrain(logits, "batch", "vocab")
        vids = j * block_v + jnp.arange(block_v)
        logits = jnp.where(vids[None, :] < V, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l_new = (l * jnp.exp(m - m_new)
                 + jnp.exp(logits - m_new[:, None]).sum(-1))
        hit = vids[None, :] == targets[:, None]
        tgt_new = tgt + jnp.where(hit, logits, 0.0).sum(-1)
        return (m_new, l_new, tgt_new), None

    m0 = jnp.full((T,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((T,), jnp.float32)
    t0 = jnp.zeros((T,), jnp.float32)
    (m, l, tgt), _ = lax.scan(body, (m0, l0, t0), (wb, jnp.arange(nb)))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return lse - tgt, lse


def _ce_fwd(hidden, w_vocab, targets, valid, block_v, ce_dtype):
    nll, lse = _ce_fwd_stats(hidden, w_vocab, targets, block_v, ce_dtype)
    loss = _masked_mean(nll, valid)
    return loss, (hidden, w_vocab, targets, valid, lse)


def _ce_bwd(block_v, ce_dtype, res, g):
    hidden, w_vocab, targets, valid, lse = res
    T, D = hidden.shape
    V = w_vocab.shape[0]
    bv = min(block_v, V)
    wp, _ = _pad_to(w_vocab, 0, bv)
    nb = wp.shape[0] // bv
    hf = hidden

    denom = (jnp.maximum(valid.sum(), 1.0) if valid is not None
             else jnp.asarray(float(T), jnp.float32))
    # per-token weight on d nll
    wtok = (valid if valid is not None else jnp.ones((T,), jnp.float32))
    coef = (g * wtok / denom)[:, None]  # (T, 1)

    def body(dh, blk):
        w_blk, j = blk
        logits = constrain(_block_logits(hf, w_blk, ce_dtype),
                           "batch", "vocab")
        vids = j * bv + jnp.arange(bv)
        probs = jnp.exp(logits - lse[:, None])
        probs = jnp.where(vids[None, :] < V, probs, 0.0)
        hit = (vids[None, :] == targets[:, None]).astype(jnp.float32)
        dlogits = (coef * (probs - hit)).astype(ce_dtype)  # (T, bv)
        dh = dh + lax.dot_general(
            dlogits, w_blk.astype(ce_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw_blk = lax.dot_general(
            dlogits, hf.astype(ce_dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bv, D)
        return dh, dw_blk

    dh0 = jnp.zeros((T, D), jnp.float32)
    wb = wp.reshape(nb, bv, D)
    dh, dwb = lax.scan(body, dh0, (wb, jnp.arange(nb)))
    dw = dwb.reshape(nb * bv, D)[:V]
    return (dh.astype(hidden.dtype), dw.astype(w_vocab.dtype), None, None)


ce_blockwise.defvjp(_ce_fwd, _ce_bwd)


# ---------------------------------------------------------------------------
# Direct CE (baseline path; fine for small vocab / smoke)
# ---------------------------------------------------------------------------


def ce_direct(hidden, w_vocab, targets, valid):
    logits = jnp.einsum("td,vd->tv", hidden.astype(jnp.float32),
                        w_vocab.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return _masked_mean(lse - tgt, valid)


# ---------------------------------------------------------------------------
# Model loss
# ---------------------------------------------------------------------------


def lm_loss(params, cfg: ModelConfig, run: RunConfig,
            batch: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token LM loss for any arch in the zoo."""
    x = registry.forward(params, cfg, run, batch)  # (B, S_total, d)
    if cfg.family == "vlm":
        x = x[:, cfg.num_img_patches:]  # loss over text positions only
    B, S, D = x.shape
    x = constrain(x, "batch", None, None)

    hidden = x.reshape(B * S, D)
    targets = batch["labels"].reshape(B * S)
    valid = batch.get("loss_mask")
    valid = valid.reshape(B * S) if valid is not None else None
    w = L.lm_head_weight(params["embed"], cfg)

    with jax.named_scope("logits_ce"):
        if run.ce_mode == "blockwise":
            loss = ce_blockwise(hidden, w, targets, valid, run.ce_block_v,
                                jnp.dtype(run.ce_dtype))
        else:
            loss = ce_direct(hidden, w, targets, valid)
    ntok = (valid.sum() if valid is not None
            else jnp.asarray(B * S, jnp.float32))
    return loss, {"loss": loss, "tokens": ntok}
