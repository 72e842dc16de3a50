"""Weights from the seed: one jitted call makes every leaf of a reference
parameter spec on the device, in the dtype it is served or trained in.

The program under test and the reference are given the same values: the
program as a nested tree in its own layout (checked leaf by leaf against
the program's parameter definitions), the reference as a flat dict.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np

from chipbench.reference.mamba2 import Spec, init_leaf


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for ``stream`` from any whole ``seed`` below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed out of range: {seed}")
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, seed >> 32)
    return jax.random.fold_in(k, stream)


def leaf_maker(spec: Spec):
    """``key -> {name: array}`` for every leaf of ``spec``; leaf ``i`` is
    drawn from ``fold_in(key, i)``, so the values do not depend on what
    else the calling program computes."""
    def make(key):
        return {name: init_leaf(jax.random.fold_in(key, i), shape, init, dt)
                for i, (name, shape, init, dt) in enumerate(spec)}
    return make


def make_params(spec: Spec, seed: int) -> Dict[str, jax.Array]:
    """Flat ``{name: array}`` made in one jitted call from ``seed``."""
    return jax.jit(leaf_maker(spec))(seed_key(seed, 1))


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, v in flat.items():
        *path, leaf = name.split("/")
        d = out
        for p in path:
            d = d.setdefault(p, {})
        d[leaf] = v
    return out


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def check_layout(spec: Spec, program_leaves: Dict[str, Any]) -> None:
    """Raise unless the program's leaves (name -> object with ``shape`` and
    ``dtype``) are exactly the spec's names, shapes and dtypes."""
    want = {n: (tuple(s), np.dtype(d)) for n, s, _, d in spec}
    have = {n: (tuple(v.shape), np.dtype(v.dtype))
            for n, v in program_leaves.items()}
    if want != have:
        diff: List[str] = sorted(
            n for n in set(want) | set(have) if want.get(n) != have.get(n))
        raise RuntimeError(
            "the program's parameter layout differs from the benchmark's "
            f"spec at {diff[:6]}: spec {[want.get(n) for n in diff[:6]]}, "
            f"program {[have.get(n) for n in diff[:6]]}")
