"""AdamW with decoupled weight decay, global-norm clipping and optional
bf16 first-moment compression (distributed-optimization trick: halves the
optimizer-state HBM footprint and the bytes moved per step).

No optax dependency — state is a plain dict pytree so the checkpointer
and the sharding rules treat it exactly like params (optimizer state is
sharded identically to its parameter: ZeRO-style).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import scoped

OptState = Dict[str, Any]


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), tree), norm


def adamw_init(params: Any, *, dtype=jnp.float32) -> OptState:
    """m/v moments shaped like params. ``dtype`` compresses the moments."""
    zeros = lambda p: jnp.zeros(p.shape, dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


@scoped("adamw")
def adamw_update(
    params: Any,
    grads: Any,
    state: OptState,
    *,
    lr,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    max_grad_norm: float = 1.0,
) -> Tuple[Any, OptState, Dict[str, jax.Array]]:
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    if max_grad_norm > 0:
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
    else:
        gnorm = global_norm(grads)

    step = state["step"] + 1
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
        mhat = m_new / c1
        vhat = v_new / c2
        delta = mhat / (jnp.sqrt(vhat) + eps)
        pf = p.astype(jnp.float32)
        # decoupled weight decay; skip 1-D params (norms / biases)
        if p.ndim >= 2:
            pf = pf - lr * weight_decay * pf
        p_new = (pf - lr * delta).astype(p.dtype)
        return p_new, m_new.astype(m.dtype), v_new.astype(v.dtype)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])

    new_state = {"m": new_m, "v": new_v, "step": step}
    metrics = {"grad_norm": gnorm, "lr": jnp.asarray(lr, jnp.float32)}
    return new_p, new_state, metrics
