"""Vocab-blockwise cross-entropy Pallas TPU kernel (forward).

Never materializes a (T, V) logit row block beyond (block_t, block_v):
grid = (T/block_t, V/block_v) with the vocab axis innermost; running
(max, sumexp, target-logit) statistics live in VMEM scratch across vocab
steps.  At 152k vocab this is the difference between 64 MB and 2.5 GB of
logits per device batch (see train/loss.py for the custom-VJP XLA twin
used in training).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _ce_kernel(h_ref, w_ref, t_ref, nll_ref, m_scr, l_scr, tgt_scr, *,
               block_t: int, block_v: int, n_v: int, V: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        tgt_scr[...] = jnp.zeros_like(tgt_scr)

    h = h_ref[...].astype(jnp.float32)          # (bT, D)
    w = w_ref[...].astype(jnp.float32)          # (bV, D)
    logits = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())))  # (bT,bV)

    vids = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    logits = jnp.where(vids < V, logits, NEG_INF)

    m_prev = m_scr[...]                         # (bT, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_new)
                  + jnp.exp(logits - m_new).sum(axis=-1, keepdims=True))
    m_scr[...] = m_new

    hit = vids == t_ref[...]                    # (bT, bV) vs (bT, 1) int32
    tgt_scr[...] = tgt_scr[...] + jnp.where(hit, logits, 0.0).sum(
        axis=-1, keepdims=True)

    @pl.when(vi == n_v - 1)
    def _emit():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        nll_ref[...] = lse - tgt_scr[...]


def cross_entropy_pallas(
    hidden: jax.Array,    # (T, D)
    w_vocab: jax.Array,   # (V, D)
    targets: jax.Array,   # (T,) int32
    valid=None,           # (T,) float/bool or None
    *,
    block_t: int = 256,
    block_v: int = 2048,
    interpret: Optional[bool] = None,
):
    T, D = hidden.shape
    V = w_vocab.shape[0]
    block_t = min(block_t, T)
    block_v = min(block_v, V)
    pad_t = (-T) % block_t
    pad_v = (-V) % block_v
    h = jnp.pad(hidden, ((0, pad_t), (0, 0))) if pad_t else hidden
    w = jnp.pad(w_vocab, ((0, pad_v), (0, 0))) if pad_v else w_vocab
    t = jnp.pad(targets, (0, pad_t)) if pad_t else targets
    Tp, Vp = h.shape[0], w.shape[0]
    n_t, n_v = Tp // block_t, Vp // block_v

    nll = pl.pallas_call(
        functools.partial(_ce_kernel, block_t=block_t, block_v=block_v,
                          n_v=n_v, V=V),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_v, D), lambda ti, vi: (vi, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)] * 3,
        interpret=resolve_interpret(interpret),
    )(h, w, t.astype(jnp.int32)[:, None])[:T, 0]

    if valid is not None:
        v = valid.astype(jnp.float32)
        return (nll * v).sum() / jnp.maximum(v.sum(), 1.0)
    return nll.mean()
