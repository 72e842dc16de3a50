"""Plain float32 training steps: masked next-token cross-entropy over the
batch, global-norm gradient clipping and AdamW (decoupled weight decay),
with the schedule and hyperparameters the configuration file states.

Gradients are summed row by row (one compiled program per row shape), so
the quadratic SSD of a whole batch is never held at once.  Parameters are
kept in the dtypes the configuration states between steps; all arithmetic
is float32.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import mamba2
from chipbench.reference.numerics import F32, Numerics


def lr_at(step: int, o: Dict[str, Any]) -> float:
    """Linear warm-up, then cosine decay to ``min_ratio`` of the base rate;
    ``step`` counts from 1."""
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    decay = o["min_ratio"] + (1 - o["min_ratio"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return o["learning_rate"] * warm * decay


def make_row_grad(model, m: Dict[str, Any], nx: Numerics):
    def row_loss(p, tokens, labels, mask):
        lg = model.logits(p, tokens, m, nx)
        return mamba2.nll_sum(lg, labels, mask)

    @jax.jit
    def row_grad(p, tokens, labels, mask):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(row_loss)(p, tokens, labels, mask)
    return row_grad


def batch_grad(row_grad, p32, batch: Dict[str, np.ndarray]):
    total = float(np.maximum(batch["loss_mask"].sum(), 1.0))
    loss, acc = 0.0, None
    for r in range(batch["tokens"].shape[0]):
        l_r, g_r = row_grad(p32, batch["tokens"][r], batch["labels"][r],
                            batch["loss_mask"][r])
        loss += float(l_r)
        acc = g_r if acc is None else jax.tree.map(jnp.add, acc, g_r)
    return loss / total, jax.tree.map(lambda g: g / total, acc)


def make_update(o: Dict[str, Any], dtypes: Dict[str, Any]):
    """AdamW step on float32 copies; returns the clipped gradient too."""
    b1, b2, eps = o["b1"], o["b2"], o["eps"]

    @jax.jit
    def update(p32, g, mom, vel, lr, step):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        g = {k: x * jnp.minimum(1.0, o["max_grad_norm"]
                                / jnp.maximum(gnorm, 1e-9))
             for k, x in g.items()}
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        mom = {k: b1 * mom[k] + (1 - b1) * x for k, x in g.items()}
        vel = {k: b2 * vel[k] + (1 - b2) * x * x for k, x in g.items()}
        new = {}
        for k, pf in p32.items():
            if pf.ndim >= o["decay_min_rank"]:
                pf = pf - lr * o["weight_decay"] * pf
            delta = (mom[k] / c1) / (jnp.sqrt(vel[k] / c2) + eps)
            new[k] = (pf - lr * delta).astype(dtypes[k])
        return new, mom, vel, g
    return update


def train_steps(params0: Dict[str, jax.Array], batches: List[Dict],
                model, m: Dict[str, Any], o: Dict[str, Any],
                nx: Numerics = F32) -> Dict[str, Any]:
    """Run ``len(batches)`` steps from ``params0`` (stored dtypes).

    Returns per-step losses, each leaf's norm of the first clipped
    gradient, and each leaf's norm of the change of the parameters after
    the last step."""
    row_grad = make_row_grad(model, m, nx)
    dtypes = {k: v.dtype for k, v in params0.items()}
    p = dict(params0)
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    vel = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    update = make_update(o, dtypes)
    losses, first_grad = [], None
    for step, batch in enumerate(batches, start=1):
        p32 = {k: v.astype(jnp.float32) for k, v in p.items()}
        loss, g = batch_grad(row_grad, p32, batch)
        losses.append(loss)
        p, mom, vel, g = update(p32, g, mom, vel, lr_at(step, o),
                                float(step))
        if first_grad is None:
            first_grad = {k: float(jnp.linalg.norm(v)) for k, v in g.items()}
        del p32, g
    change = {k: float(jnp.linalg.norm(p[k].astype(jnp.float32)
                                       - params0[k].astype(jnp.float32)))
              for k in p}
    return {"losses": losses, "first_grad": first_grad, "change": change}
