"""Mesh construction (functions, not module constants: importing this
module never touches jax device state).

Production target: TPU v5e pods, 256 chips each, 16x16 (data, model)
per pod; the multi-pod mesh adds a leading "pod" axis over DCN.

Every mesh has Auto axes: the model code places tensors through
``lax.with_sharding_constraint`` (``sharding.constrain``) and leaves the
rest to GSPMD propagation, which Explicit axes (the ``jax.make_mesh``
default since jax 0.7) refuse.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """``devices`` defaults to those of the default backend; the mesh
    takes the first ``prod(shape)`` of them."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence[jax.Device]] = None
                         ) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices=devices)


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """(data, model) mesh over every device of the default backend (CPU
    smoke: 1 device; one TPU v5e host: 1 or 4 chips)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link (~per-chip usable)
HBM_BYTES = 16e9              # 16 GB
