"""Training step: grads (with microbatch accumulation) + AdamW update.

The state is a plain dict pytree — params, optimizer moments, step — so
sharding/checkpointing treat everything uniformly.  ``make_train_step``
returns a pure ``(state, batch) -> (state, metrics)`` for jit; the launch
layer wraps it with in/out shardings resolved from the param defs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RunConfig
from repro.models import params as P
from repro.models import registry
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.sharding import ShardingRules, param_shardings
from repro.train.loss import lm_loss

TrainState = Dict[str, Any]


def state_shardings(cfg: ModelConfig, rules: ShardingRules) -> TrainState:
    """NamedSharding tree of the train state: each moment is sharded
    exactly like its parameter (ZeRO-style); the step is replicated."""
    p_sh = param_shardings(registry.param_defs(cfg), rules)
    return {"params": p_sh,
            "opt": {"m": p_sh, "v": p_sh, "step": rules.sharding((), ())}}


def init_state(rng: jax.Array, cfg: ModelConfig, run: RunConfig,
               shardings: Any = None) -> TrainState:
    """Params and moments, created under jit so that every leaf is born
    with its sharding (``shardings`` from ``state_shardings``) and no
    device ever holds the whole state; None leaves placement to jit."""
    def init(key):
        params = P.materialize(key, registry.param_defs(cfg))
        opt = adamw_init(params, dtype=jnp.dtype(run.opt_state_dtype))
        return {"params": params, "opt": opt}
    return jax.jit(init, out_shardings=shardings)(rng)


def abstract_state(cfg: ModelConfig, run: RunConfig) -> TrainState:
    """ShapeDtypeStruct state tree (dry-run: no allocation)."""
    defs = registry.param_defs(cfg)
    params = P.abstract(defs)
    dt = jnp.dtype(run.opt_state_dtype)
    mom = P.tree_map(lambda d: jax.ShapeDtypeStruct(d.shape, dt), defs)
    return {
        "params": params,
        "opt": {"m": mom, "v": jax.tree.map(lambda x: x, mom),
                "step": jax.ShapeDtypeStruct((), jnp.int32)},
    }


def _split_microbatches(batch: Dict[str, Any], accum: int) -> Dict[str, Any]:
    def split(x):
        B = x.shape[0]
        assert B % accum == 0, (B, accum)
        return x.reshape(accum, B // accum, *x.shape[1:])
    return jax.tree.map(split, batch)


def grads_and_metrics(params, cfg: ModelConfig, run: RunConfig,
                      batch: Dict[str, Any]):
    """Value-and-grad with optional lax.scan gradient accumulation."""
    loss_fn = lambda p, b: lm_loss(p, cfg, run, b)

    if run.accum_steps <= 1:
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return grads, {"loss": loss, **aux}

    mb = _split_microbatches(batch, run.accum_steps)

    def body(carry, mbatch):
        g_acc, l_acc = carry
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mbatch)
        g_acc = jax.tree.map(
            lambda a, b: a + b.astype(a.dtype), g_acc, g)
        return (g_acc, l_acc + loss), None

    g0 = jax.tree.map(  # accumulate in fp32 regardless of param dtype
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (g_sum, l_sum), _ = lax.scan(body, (g0, jnp.zeros((), jnp.float32)), mb)
    inv = 1.0 / run.accum_steps
    grads = jax.tree.map(lambda g: (g * inv).astype(g.dtype), g_sum)
    return grads, {"loss": l_sum * inv}


def train_step(state: TrainState, batch: Dict[str, Any], *,
               cfg: ModelConfig, run: RunConfig
               ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    params, opt = state["params"], state["opt"]
    grads, metrics = grads_and_metrics(params, cfg, run, batch)

    if run.grad_compression == "bf16":
        # compress gradients before the data-axis reduction GSPMD inserts;
        # halves all-reduce bytes (see EXPERIMENTS.md §Perf)
        grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)

    lr = cosine_schedule(opt["step"] + 1, base_lr=run.learning_rate,
                         warmup_steps=run.warmup_steps,
                         total_steps=run.total_steps)
    new_params, new_opt, opt_metrics = adamw_update(
        params, grads, opt, lr=lr,
        weight_decay=run.weight_decay,
        max_grad_norm=run.max_grad_norm)
    metrics.update(opt_metrics)
    return {"params": new_params, "opt": new_opt}, metrics


def make_train_step(cfg: ModelConfig, run: RunConfig):
    """Closure suitable for jax.jit(in_shardings=..., out_shardings=...)."""
    return functools.partial(train_step, cfg=cfg, run=run)
