"""Logical-axis -> mesh-axis resolution with divisibility fallbacks.

Model code never names mesh axes directly; it tags tensor dims with
logical names ("batch", "heads", "ffn", ...).  A ``ShardingRules`` context
resolves those names against a concrete mesh, dropping any mapping whose
dimension is not divisible by the mesh-axis size (this is what makes the
40-head / 20-head / 6-head architectures shard cleanly: the "heads" rule
silently drops and the flattened "qkv" / "kv_seq" rules still apply).

Default physical mapping:

  batch   -> ("pod", "data")     activations' batch dim (DP across pods)
  embed   -> ("data",)           weight d_model dim (FSDP / ZeRO-3 style)
  heads   -> ("model",)          attention heads (TP)
  qkv     -> ("model",)          flattened q/k/v feature dim (TP)
  ffn     -> ("model",)          MLP hidden (TP)
  vocab   -> ("model",)          embedding/vocab rows (TP)
  expert  -> ("model",)          MoE experts (EP)
  lora    -> ("data",)           an adapter's rank dim in its up-projection
                                 (FSDP: the one dim of that weight that
                                 is not tensor-parallel)
  kv_seq  -> ("model",)          KV sequence inside attention, ONLY for
                                 archs whose head count doesn't divide
                                 (flash-decoding-style partial softmax)
  layers  -> ()                  stacked-layer dim, never sharded
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import params as P

DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),
    "heads": ("model",),
    "heads_ssm": ("model",),
    "ssm_p": ("model",),  # SSD head_dim fallback when heads don't divide
    "qkv": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "lora": ("data",),
    "kv_seq": ("model",),
    "q_seq": ("model",),
    "layers": (),
    "seq": (),
}

_TLS = threading.local()


class ShardingRules:
    def __init__(self, mesh: Mesh,
                 rules: Optional[Dict[str, Tuple[str, ...]]] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    def axis_size(self, axes: Tuple[str, ...]) -> int:
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def resolve_dim(self, name: Optional[str], dim: int) -> Optional[Any]:
        """Mesh axes for one tensor dim, or None (replicated)."""
        if name is None:
            return None
        axes = tuple(a for a in self.rules.get(name, ())
                     if a in self.mesh.shape)
        if not axes:
            return None
        if dim % self.axis_size(axes) != 0:
            # divisibility fallback: try a prefix of the axes, else replicate
            for k in range(len(axes) - 1, 0, -1):
                sub = axes[:k]
                if dim % self.axis_size(sub) == 0:
                    return sub if len(sub) > 1 else sub[0]
            return None
        return axes if len(axes) > 1 else axes[0]

    def spec(self, logical: Sequence[Optional[str]],
             shape: Sequence[int]) -> PartitionSpec:
        """Resolve logical names, dropping duplicate mesh-axis uses (first
        dim wins) — lets e.g. MoE weights carry both "expert" and "ffn"
        logical tags and shard on whichever the arch's sizes allow."""
        assert len(logical) == len(shape), (logical, shape)
        resolved = []
        used: set = set()
        for n, d in zip(logical, shape):
            r = self.resolve_dim(n, d)
            if r is None:
                resolved.append(None)
                continue
            axes = r if isinstance(r, tuple) else (r,)
            if any(a in used for a in axes):
                resolved.append(None)
                continue
            used.update(axes)
            resolved.append(r)
        return PartitionSpec(*resolved)

    def sharding(self, logical, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))


@contextlib.contextmanager
def use_rules(rules: Optional[ShardingRules]):
    prev = getattr(_TLS, "rules", None)
    _TLS.rules = rules
    try:
        yield rules
    finally:
        _TLS.rules = prev


def current_rules() -> Optional[ShardingRules]:
    return getattr(_TLS, "rules", None)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint by logical names; no-op without context."""
    r = current_rules()
    if r is None:
        return x
    return lax.with_sharding_constraint(x, r.sharding(logical, x.shape))


# ---------------------------------------------------------------------------
# Param-tree helpers
# ---------------------------------------------------------------------------


def param_specs(defs: Any, rules: ShardingRules) -> Any:
    """PartitionSpec tree for a ParamDef tree."""
    return P.tree_map(lambda d: rules.spec(d.logical, d.shape), defs)


def param_shardings(defs: Any, rules: ShardingRules) -> Any:
    return P.tree_map(lambda d: rules.sharding(d.logical, d.shape), defs)


def batch_shardings(rules: ShardingRules, batch: Any) -> Any:
    """Input-batch shardings: every leaf's leading dim on the "batch" rule,
    the rest replicated (scalars replicated).  Leaves need only ``.shape``,
    so this serves abstract specs and host arrays alike."""
    def one(x):
        logical = ("batch",) + (None,) * (len(x.shape) - 1) if x.shape else ()
        return rules.sharding(logical, x.shape)
    return jax.tree.map(one, batch)
