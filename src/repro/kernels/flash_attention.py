"""Flash-attention Pallas TPU kernel (forward).

TPU-native adaptation of the CUDA flash algorithm:
  * grid = (batch*q_heads, Sq/block_q, Sk/block_k); the KV dim is the
    innermost (sequential) grid axis, so the online-softmax running
    statistics (m, l) and the output accumulator live in VMEM scratch and
    persist across KV steps — the TPU analogue of a CUDA thread-block's
    shared-memory accumulators.
  * block shapes are MXU-aligned: (block_q, D) x (block_k, D) tiles with
    D = head_dim (128 on every assigned arch except whisper's 64).
  * GQA is handled in the BlockSpec index_map (q head -> kv head), so K/V
    tiles are fetched once per kv head group, not per q head repeat.
  * causal / sliding-window / cache-length masks are computed on the fly
    from iota — no mask tensor ever materializes.

Training uses kernels/ref.py (same math, custom O(S) VJP); this kernel is
the serving/prefill fast path and the per-shape validation target
(tests/test_kernels.py sweeps shapes x dtypes against the ref oracle).
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


def _flash_kernel(q_ref, k_ref, v_ref, qo_ref, kl_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, sliding_window: int,
                  block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)                  # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    q_pos = qo_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kl_ref[0]
    if causal:
        mask = mask & (k_pos <= q_pos)
    if sliding_window:
        mask = mask & (k_pos > q_pos - sliding_window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot(p.astype(v.dtype), v))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset=0,
    kv_len=None,
    sliding_window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % max(Hkv, 1) == 0
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)

    # (B, H, S, D) layout: contiguous (S, D) tiles per (batch, head)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sqp, Skp = qt.shape[2], kt.shape[2]
    n_q, n_k = Sqp // block_q, Skp // block_k

    qt = qt.reshape(B * Hq, Sqp, D)
    kt = kt.reshape(B * Hkv, Skp, D)
    vt = vt.reshape(B * Hkv, Skp, D)

    qo = jnp.full((1,), q_offset, jnp.int32)
    kl = jnp.full((1,), Sk if kv_len is None else kv_len, jnp.int32)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, block_q=block_q, block_k=block_k,
        n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda h, qi, ki, G=G: (h // G, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda h, qi, ki, G=G: (h // G, ki, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt, qo, kl)

    out = out.reshape(B, Hq, Sqp, D)[:, :, :Sq].transpose(0, 2, 1, 3)
    return out
