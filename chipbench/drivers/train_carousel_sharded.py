"""Training fed by the data carousel with the state sharded over several
chips: ``train_carousel``'s run, with the program's ``run_training`` given
a mesh of the traffic's ``mesh`` shape (("data", "model")), the seeded
weights made sharded as the program shards them, and the float32
reference (``reference/zamba2.py``) trained with its state sharded over
the same chips (``reference/train_sharded.py``); the model's operations
are ``flops_hybrid.py``'s.  Tracing also reduces the trace by scope and by
collective over every chip (``trace_scoped.py``).

As in ``train_carousel``: the first ``checked_steps`` steps are set-up and
checked, the window follows, and the harness binds ``build_cold_store``,
``init_state`` and ``make_carousel_pipeline`` of ``repro.launch.train``
and reads the state from its frame in ``on_step``.
"""
from __future__ import annotations

import math
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench import corpus as C
from chipbench import flops_hybrid
from chipbench import harness as H
from chipbench import scopes
from chipbench import weights as W
from chipbench.drivers.train_carousel import Feed, _leaf_norms, gaps
from chipbench.reference import train_sharded
from chipbench.reference import zamba2 as ref_model
from chipbench.trace_scoped import ScopedTracer


def _change_norms(params, params0):
    return {k: jnp.linalg.norm(a.astype(jnp.float32)
                               - params0[k].astype(jnp.float32))
            for k, a in W.flatten(params).items()}


def reference_readings(spec, m, conf, seed, batches, mesh, nx=None):
    """The float32 reference's losses, first clipped gradient and change,
    trained from the seed's weights on ``batches``, sharded over ``mesh``;
    ``nx`` as in ``train_carousel.reference_readings``."""
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.dtype(d)) for n, s, _, d in spec}
    params0 = jax.jit(W.leaf_maker(spec),
                      out_shardings=train_sharded.leaf_shardings(
                          shapes, mesh))(W.seed_key(seed, 1))
    o = dict(conf["train"]["run"], **conf["train"]["adamw"])
    kw = {} if nx is None else {"nx": nx}
    return train_sharded.train_steps(params0, batches, ref_model, m, o,
                                     mesh, **kw)


def run(r: H.Run, counter: H.CompileCounter) -> None:
    import repro.launch.train as T
    from repro.carousel.storage import ColdStore, TapeFile
    from repro.configs.base import RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import registry
    from repro.optim import adamw_init
    from repro.sharding import ShardingRules
    from repro.train.step import state_shardings

    conf, traffic = r.cell.config, r.cell.traffic
    m = conf["model"]
    cfg = H.program_config(conf)
    run_cfg = RunConfig(**conf["train"]["run"])
    spec = ref_model.param_spec(m)
    W.check_layout(spec, W.flatten(registry.param_defs(cfg)))
    checked = traffic["checked_steps"]
    data = C.Corpus(r.seed, traffic, m["vocab_size"])
    mesh = make_mesh(tuple(traffic["mesh"]), ("data", "model"))
    param_sh = state_shardings(cfg, ShardingRules(mesh))["params"]
    tracer = ScopedTracer(r)
    gc_pauses = H.GcPauses()
    made: Dict[str, Any] = {}

    def seeded_cold_store(*, n_shards, **_program_defaults):
        cold = ColdStore(drives=traffic["tape_drives"],
                         mount_latency=traffic["tape_latency_s"],
                         fault_rate=traffic["fault_rate"],
                         seed=r.seed & 0x7FFFFFFF)
        size = traffic["docs_per_shard"] * traffic["mean_doc_len"] * 4
        for s in range(n_shards):
            cold.add(TapeFile(name=f"shard-{s:05d}", size=size,
                              generator=lambda s=s: data.docs(s)))
        made["n_shards"] = n_shards
        return cold

    def seeded_init_state(_rng, cfg_, run_, shardings=None):
        leaves = W.leaf_maker(spec)

        def init(key):
            params = W.nest(leaves(key))
            return {"params": params,
                    "opt": adamw_init(params,
                                      dtype=jnp.dtype(run_.opt_state_dtype))}
        return jax.jit(init, out_shardings=shardings)(W.seed_key(r.seed, 1))

    def wrapped_pipeline(*a, **kw):
        stager, delivery = original_pipeline(*a, **kw)
        made["delivery"] = delivery
        made["feed"] = Feed(delivery, checked, r.trace)
        return stager, made["feed"]

    readings: Dict[str, Any] = {}
    times: List[float] = []
    clock: Dict[str, float] = {}
    b1 = conf["train"]["adamw"]["b1"]
    norms = jax.jit(_leaf_norms)
    change_norms = jax.jit(_change_norms)
    params0 = jax.jit(lambda k: W.nest(W.leaf_maker(spec)(k)),
                      out_shardings=param_sh)

    def on_step(done: int, _info: Dict[str, float]) -> None:
        t = H.now()
        times.append(t)
        if done == 1 or done == checked:
            state = sys._getframe(1).f_locals["state"]
            if done == 1:
                readings["first_grad"] = {
                    k: float(v) / (1 - b1) for k, v in
                    norms(state["opt"]["m"]).items()}
            if done == checked:
                p0 = W.flatten(params0(W.seed_key(r.seed, 1)))
                readings["change"] = {
                    k: float(v) for k, v in
                    change_norms(state["params"], p0).items()}
                del p0
                r.setup_s = H.now() - r.t0
                counter.count, counter.counting = 0, True
                gc_pauses.open()
                tracer.start()
                clock["open"] = H.now()
            return
        if "open" in clock and t >= clock["open"] + r.seconds:
            made["feed"].stop = True
            tracer.end_window()
            counter.counting = False
            gc_pauses.close()
            clock["close"] = t

    originals = (T.build_cold_store, T.init_state, T.make_carousel_pipeline)
    original_pipeline = T.make_carousel_pipeline
    T.build_cold_store = seeded_cold_store
    T.init_state = seeded_init_state
    T.make_carousel_pipeline = wrapped_pipeline
    steps = checked + 1 + math.ceil(r.seconds / traffic["fastest_step_s"])
    try:
        out = T.run_training(
            cfg.name, smoke=False, steps=steps,
            seq_len=traffic["seq_len"], global_batch=traffic["global_batch"],
            carousel=True, coarse=traffic["coarse"],
            tape_latency=traffic["tape_latency_s"],
            drives=traffic["tape_drives"], run=run_cfg, on_step=on_step,
            mesh=mesh)
    finally:
        (T.build_cold_store, T.init_state,
         T.make_carousel_pipeline) = originals
        counter.counting = False
        gc_pauses.close()
    tracer.stop(r, scopes.SPANS)
    r.memory_peak_bytes = H.memory_peak_bytes(r.cell.workload["chips"])
    losses = out.pop("losses")
    del out["state"], out
    feed, delivery = made["feed"], made["delivery"]
    if "close" not in clock:        # the program ran out of steps first
        clock["close"] = times[-1]

    n = len(times)
    win = slice(checked, n)
    r.window_s = clock["close"] - clock["open"]
    r.window_compiles = counter.count
    r.attempted = n - checked
    r.failed = sum(1 for x in losses[win] if not math.isfinite(x))
    peak = H.peak(jax.devices()[0].device_kind)
    r.counters.update({
        "trained_tokens": sum(feed.trained[win]),
        "input_wait_s": sum(feed.wait_s[win]),
        "steps": r.attempted,
        "flops_per_token": flops_hybrid.train_flops_per_token(
            m, traffic["seq_len"]),
        "peak_flops": peak["bf16_flops"] * r.cell.workload["chips"],
    })
    step_s = [b - a for a, b in zip(times[checked - 1:], times[checked:])]
    print(f"train: steps={n} window_steps={r.attempted} "
          f"window_s={r.window_s} step_s={step_s} "
          f"trained={feed.trained[win]} losses[:4]={losses[:4]} "
          f"batch_shapes={sorted(feed.shapes)} "
          f"failed_shards={delivery.failed_shards} "
          f"staged_shards={made['n_shards']}", file=sys.stderr)
    print(gc_pauses.summary(), file=sys.stderr)

    t_ref = H.now()
    check(r, feed, readings, losses[:checked], data, made, spec, mesh)
    print(f"train: reference_s={H.now() - t_ref}", file=sys.stderr)


def check(r: H.Run, feed: Feed, readings, losses, data, made, spec,
          mesh) -> None:
    conf, m = r.cell.config, r.cell.config["model"]
    ref = reference_readings(spec, m, conf, r.seed, feed.kept, mesh)
    prog = {"losses": losses, **readings}
    g = gaps(prog, ref)
    r.compared = {"batches": feed.kept, "reference": ref, "program": prog,
                  "mesh": mesh}
    print(f"train: program losses={losses} reference losses="
          f"{ref['losses']}", file=sys.stderr)
    lim = r.cell.limits
    for name in ("loss_gap", "grad_gap", "change_gap"):
        r.check(name, g[name], lim[name])
    skipped = set(made["delivery"].skipped_shards)
    staged = [s for s in range(made["n_shards"])
              if f"shard-{s:05d}" not in skipped]
    r.check("rows_not_staged",
            C.rows_not_staged(feed.digests, data, staged),
            lim["rows_not_staged"])
    r.check("window_compiles", r.window_compiles, lim["window_compiles"])
