"""Fused RMSNorm Pallas TPU kernel.

One pass over the rows: each grid step loads a (block_rows, D) tile into
VMEM, computes the row-wise RMS statistic in f32 on the VPU, scales by the
(replicated) weight vector, and writes the normalized tile — no f32
intermediate ever round-trips to HBM (the XLA ref materializes x.astype
(f32) at CPU fusion boundaries).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)          # (bR, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                   block_rows: int = 256, interpret: Optional[bool] = None
                   ) -> jax.Array:
    """x: (..., D); w: (D,)."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    bR = min(block_rows, R)
    pad = (-R) % bR
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    Rp = xf.shape[0]

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(Rp // bR,),
        in_specs=[
            pl.BlockSpec((bR, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bR, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, D), x.dtype),
        interpret=resolve_interpret(interpret),
    )(xf, w)
    return out[:R].reshape(orig_shape)
