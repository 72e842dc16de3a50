"""The float32 training steps of ``train.py`` for a model whose float32
state does not fit one chip: parameters, gradients and both moments are
held sharded over the "data" axis of a mesh (``leaf_shardings``), and the
rows of a batch are taken as many at a time as the mesh has chips, one on
each (``vmap``), the weights gathered as the compiler chooses.  The
arithmetic is ``train.py``'s: the batch's gradient is the sum of its rows'
over the trained tokens, then clipping and AdamW (``train.make_update``)."""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from chipbench.reference import mamba2
from chipbench.reference.numerics import F32, Numerics
from chipbench.reference.train import lr_at, make_update


def leaf_shardings(shapes: Dict[str, Any], mesh: Mesh
                   ) -> Dict[str, NamedSharding]:
    """Each matrix on its largest axis that the "data" axis divides; leaves
    under a million values (norms, biases, per-head vectors) replicated."""
    n = mesh.shape["data"]

    def one(shape):
        spec = [None] * len(shape)
        if np.prod(shape) >= 2 ** 20:
            for ax in sorted(range(len(shape)), key=lambda a: -shape[a]):
                if shape[ax] % n == 0:
                    spec[ax] = "data"
                    break
        return NamedSharding(mesh, PartitionSpec(*spec))
    return {k: one(tuple(v.shape)) for k, v in shapes.items()}


def make_rows_grad(model, m: Dict[str, Any], nx: Numerics, shardings):
    def rows_loss(p, tokens, labels, mask):
        lg = jax.vmap(lambda t: model.logits(p, t, m, nx))(tokens)
        return jnp.sum(jax.vmap(mamba2.nll_sum)(lg, labels, mask))

    @jax.jit
    def rows_grad(p, tokens, labels, mask):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(rows_loss)(p, tokens, labels, mask)
        return loss, jax.lax.with_sharding_constraint(g, shardings)
    return rows_grad


def train_steps(params0: Dict[str, jax.Array], batches: List[Dict],
                model, m: Dict[str, Any], o: Dict[str, Any], mesh: Mesh,
                nx: Numerics = F32) -> Dict[str, Any]:
    """``train.train_steps`` with the state sharded over ``mesh``; the
    same readings: per-step losses, each leaf's norm of the first clipped
    gradient and of the parameters' change after the last step."""
    sh = leaf_shardings(params0, mesh)
    rows = NamedSharding(mesh, PartitionSpec("data"))
    n_rows = mesh.shape["data"]
    rows_grad = make_rows_grad(model, m, nx, sh)
    dtypes = {k: v.dtype for k, v in params0.items()}
    p = jax.device_put(params0, sh)
    zeros = jax.jit(lambda t: {k: jnp.zeros(v.shape, jnp.float32)
                               for k, v in t.items()}, out_shardings=sh)
    mom, vel = zeros(p), zeros(p)
    update = make_update(o, dtypes)
    to32 = jax.jit(lambda t: {k: v.astype(jnp.float32) for k, v in t.items()},
                   out_shardings=sh)
    # the sum and the mean are made in the buffers of the running sum
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), out_shardings=sh,
                  donate_argnums=(0,))
    scale = jax.jit(lambda t, s: jax.tree.map(lambda g: g / s, t),
                    out_shardings=sh, donate_argnums=(0,))
    losses, first_grad = [], None
    for step, batch in enumerate(batches, start=1):
        p32 = to32(p)
        del p
        total = float(np.maximum(batch["loss_mask"].sum(), 1.0))
        loss, acc = 0.0, None
        for r0 in range(0, batch["tokens"].shape[0], n_rows):
            part = [jax.device_put(np.asarray(batch[k][r0:r0 + n_rows]),
                                   rows)
                    for k in ("tokens", "labels", "loss_mask")]
            l_r, g_r = rows_grad(p32, *part)
            loss += float(l_r)
            acc = g_r if acc is None else add(acc, g_r)
        losses.append(loss / total)
        g = scale(acc, total)
        del acc
        p, mom, vel, g = update(p32, g, mom, vel, lr_at(step, o),
                                float(step))
        if first_grad is None:
            first_grad = {k: float(jnp.linalg.norm(v)) for k, v in g.items()}
        del p32, g
    change = {k: float(jnp.linalg.norm(p[k].astype(jnp.float32)
                                       - params0[k].astype(jnp.float32)))
              for k in p}
    return {"losses": losses, "first_grad": first_grad, "change": change}
