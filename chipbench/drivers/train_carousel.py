"""Training fed by the data carousel: drives
``repro.launch.train.run_training(carousel=True)`` itself, in one call.

The call's first ``checked_steps`` steps are set-up: the first compiles,
and after the first and the last of them the harness reads the train
state to compare with the reference.  The steps after them are the
measured window; it closes after the first step that ends ``seconds``
after it opened, and the feed then ends, so ``run_training`` leaves its
loop and shuts its stager down itself.  The call is sized for steps no
shorter than the traffic's ``fastest_step_s``: the program stages shards
for the steps it is asked for.

``run_training`` takes no seed and exposes neither its state nor its
feed, so the harness binds three module-level names it calls:
``build_cold_store`` (the corpus of ``chipbench.corpus``, from the seed),
``init_state`` (weights from the seed, moments from the program's
``adamw_init``) and ``make_carousel_pipeline`` (the delivery iterator is
wrapped to time ``next()`` and to end the feed); its state is read from
its frame in ``on_step``.
"""
from __future__ import annotations

import collections
import math
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import corpus as C
from chipbench import flops
from chipbench import harness as H
from chipbench import weights as W
from chipbench.reference import mamba2 as ref_model
from chipbench.reference import train as ref_train

SPANS = ("input",)


class Feed:
    """The delivery iterator as ``run_training`` consumes it: times each
    ``next()`` (span ``input``), keeps what the checks need, and ends when
    ``stop`` is set."""

    def __init__(self, delivery, keep: int, tracing: bool):
        self.delivery = delivery
        self.keep = keep
        self.tracing = tracing
        self.stop = False
        self.wait_s: List[float] = []
        self.trained: List[float] = []
        self.kept: List[Dict[str, np.ndarray]] = []
        self.digests: collections.Counter = collections.Counter()
        self.shapes = set()

    def __iter__(self):
        it = iter(self.delivery)
        while not self.stop:
            with H.span("input", self.tracing):
                t0 = H.now()
                b = next(it, None)
                self.wait_s.append(H.now() - t0)
            if b is None:
                return
            self.trained.append(float(b["loss_mask"].sum()))
            self.shapes.add(b["tokens"].shape)
            self.digests.update(C.row_digests(b))
            if len(self.kept) < self.keep:
                self.kept.append({k: np.array(v) for k, v in b.items()})
            yield b


def _leaf_norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.linalg.norm(v.astype(jnp.float32))
            for k, v in W.flatten(tree).items()}


def run(r: H.Run, counter: H.CompileCounter) -> None:
    import repro.launch.train as T
    from repro.carousel.storage import ColdStore, TapeFile
    from repro.configs.base import RunConfig
    from repro.models import registry
    from repro.optim import adamw_init

    conf, traffic = r.cell.config, r.cell.traffic
    m = conf["model"]
    cfg = H.program_config(conf)
    run_cfg = RunConfig(**conf["train"]["run"])
    spec = ref_model.param_spec(m)
    W.check_layout(spec, W.flatten(registry.param_defs(cfg)))
    checked = traffic["checked_steps"]
    data = C.Corpus(r.seed, traffic, m["vocab_size"])
    tracer = H.Tracer(r)
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
    params0 = jax.jit(lambda k: W.nest(W.leaf_maker(spec)(k)))

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
                p0 = params0(W.seed_key(r.seed, 1))
                diff = jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(
                        jnp.float32), state["params"], p0)
                readings["change"] = {k: float(v) for k, v in
                                      norms(diff).items()}
                del p0, diff
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
            drives=traffic["tape_drives"], run=run_cfg, on_step=on_step)
    finally:
        (T.build_cold_store, T.init_state,
         T.make_carousel_pipeline) = originals
        counter.counting = False
        gc_pauses.close()
    tracer.stop(r, SPANS)
    r.memory_peak_bytes = H.memory_peak_bytes(r.cell.workload["chips"])
    losses = out.pop("losses")
    del out["state"], out
    feed, delivery = made["feed"], made["delivery"]
    if "close" not in clock:        # the program ran out of steps first
        clock["close"] = times[-1]

    # the window: steps checked+1 .. n, where n is the last step run
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
        "flops_per_token": flops.train_flops_per_token(m),
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

    # the reference, once the program's state is gone
    t_ref = H.now()
    check(r, feed, readings, losses[:checked], data, made, spec)
    print(f"train: reference_s={H.now() - t_ref}", file=sys.stderr)


def reference_readings(spec, m, conf, seed, batches, nx=None):
    params0 = W.make_params(spec, seed)
    o = dict(conf["train"]["run"], **conf["train"]["adamw"])
    kw = {} if nx is None else {"nx": nx}
    return ref_train.train_steps(params0, batches, ref_model, m, o, **kw)


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared: the widest loss gap over the checked steps;
    for the first clipped gradient and for the change of the parameters
    after the checked steps, the worst leaf's gap between the two norms,
    over the larger of the reference's norm of that leaf and of the median
    leaf.  Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the change."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                              ref["losses"]))
    g_ref = ref["first_grad"]
    g_med = float(np.median(list(g_ref.values())))
    moved = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    c_ref = ref["change"]
    c_med = float(np.median([c_ref[k] for k in moved]))

    def worst(p, q, keys, med):
        return max(abs(p[k] - q[k]) / max(q[k], med) for k in keys)
    return {"loss_gap": loss_gap,
            "grad_gap": worst(prog["first_grad"], g_ref, list(g_ref), g_med),
            "change_gap": worst(prog["change"], c_ref, moved, c_med)}


def check(r: H.Run, feed: Feed, readings, losses, data, made, spec) -> None:
    conf, m = r.cell.config, r.cell.config["model"]
    ref = reference_readings(spec, m, conf, r.seed, feed.kept)
    prog = {"losses": losses, **readings}
    g = gaps(prog, ref)
    r.compared = {"batches": feed.kept, "reference": ref, "program": prog}
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
