"""Mamba2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

The SSD insight — the recurrence factors into a block-diagonal intra-chunk
part (dense (Q,Q) matmuls, MXU food) plus a low-rank inter-chunk state
carry — maps directly onto a TPU grid:

  grid = (batch*heads, n_chunks), chunk dim innermost/sequential.
  Per step: load a (Q,P) x-tile + (Q,N) B/C tiles into VMEM, run the
  decay-weighted (Q,Q)@(Q,P) intra-chunk matmul, read/update the (P,N)
  running state held in VMEM scratch (persists across the chunk axis,
  like a flash-attention accumulator).

Q = chunk = 128 keeps every matmul MXU-shaped.  Zero-padding the tail is
algebraically safe: padded dt = 0 gives decay 1 and no state injection.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, h0_ref,
                y_ref, hout_ref, h_scr, *, H: int, n_c: int, chunk: int):
    bh = pl.program_id(0)
    ci = pl.program_id(1)
    h_idx = jax.lax.rem(bh, H)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)     # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)   # (1, Q) lane-major row
    Bm = B_ref[0, 0].astype(jnp.float32)    # (Q, N)
    Cm = C_ref[0, 0].astype(jnp.float32)    # (Q, N)
    A = A_ref[h_idx]                        # scalar (negative)

    # Row/column views of the per-step vectors come from masked reductions
    # over (Q, Q) iotas: exact in f32, and no 1-D or transposed layouts.
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = j_idx <= i_idx
    eye = i_idx == j_idx
    dA = dt * A                                                  # (1, Q)
    cs = jnp.sum(jnp.where(tri, dA, 0.0), axis=1, keepdims=True)  # (Q, 1)
    cs_row = jnp.sum(jnp.where(eye, cs, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(eye, dt, 0.0), axis=1, keepdims=True)
    total = jnp.sum(dA, axis=1, keepdims=True)                   # (1, 1)

    # intra-chunk decay matrix L[i,j] = exp(cs_i - cs_j) for j <= i
    L = jnp.where(tri, jnp.exp(cs - cs_row), 0.0)

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q, Q)
    M = CB * L * dt
    y_intra = jax.lax.dot(M, x)                                  # (Q, P)

    h = h_scr[...]                                               # (P, N)
    y_inter = jax.lax.dot_general(Cm * jnp.exp(cs), h,
                                  (((1,), (1,)), ((), ())))      # (Q, P)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(sum dA) h + sum_s dt_s decay_end_s x_s B_s^T
    decay_end = jnp.exp(total - cs)                              # (Q, 1)
    xw = x * (dt_col * decay_end)                                # (Q, P)
    h_scr[...] = (h * jnp.exp(total)
                  + jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ()))))

    @pl.when(ci == n_c - 1)
    def _emit():
        hout_ref[0] = h_scr[...]


def ssd_pallas(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) positive
    A: jax.Array,    # (H,) negative
    Bm: jax.Array,   # (B, S, G, N)
    Cm: jax.Array,   # (B, S, G, N)
    *,
    chunk: int = 128,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
    return_state: bool = False,
    interpret: Optional[bool] = None,
):
    B_, S, H, P = x.shape
    _, _, G, N = Bm.shape
    assert H % G == 0
    HG = H // G
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = x.shape[1]
    n_c = Sp // chunk

    xt = x.transpose(0, 2, 1, 3).reshape(B_ * H, n_c, chunk, P)
    dtt = dt.transpose(0, 2, 1).reshape(B_ * H, n_c, 1, chunk)
    Bt = Bm.transpose(0, 2, 1, 3).reshape(B_ * G, n_c, chunk, N)
    Ct = Cm.transpose(0, 2, 1, 3).reshape(B_ * G, n_c, chunk, N)
    h0 = (jnp.zeros((B_ * H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32).reshape(B_ * H, P, N))

    def kv_map(bh, ci, H=H, HG=HG, G=G):
        return ((bh // H) * G + (bh % H) // HG, ci, 0, 0)

    kernel = functools.partial(_ssd_kernel, H=H, n_c=n_c, chunk=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=(B_ * H, n_c),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), kv_map),
            pl.BlockSpec((1, 1, chunk, N), kv_map),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_ * H, n_c, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B_ * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xt, dtt, A.astype(jnp.float32), Bt, Ct, h0)

    y = y.reshape(B_, H, Sp, P).transpose(0, 2, 1, 3)[:, :S]
    if return_state:
        return y, hout.reshape(B_, H, P, N)
    return y
