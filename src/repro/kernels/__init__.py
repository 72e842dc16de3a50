"""Pallas TPU kernels, their pure-jnp references (``ref.py``) and the
dispatch layer the models call (``ops.py``)."""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode: an explicit value
    wins; otherwise only where the default backend is the CPU (Mosaic
    compiles the kernel on the TPU)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
