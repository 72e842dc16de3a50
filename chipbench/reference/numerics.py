"""Precision of the reference's matrix products."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


@dataclass(frozen=True)
class Numerics:
    """``operand_dtype`` None: float32 products at HIGHEST precision.
    Otherwise every operand of a product is rounded to that dtype first
    (gradients flow back through the same rounding)."""

    operand_dtype: Optional[str] = None

    def q(self, x):
        if self.operand_dtype is None:
            return x
        return x.astype(self.operand_dtype).astype(jnp.float32)

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def einsum(self, spec, *xs):
        return jnp.einsum(spec, *(self.q(x) for x in xs), precision=HIGHEST)


F32 = Numerics()
