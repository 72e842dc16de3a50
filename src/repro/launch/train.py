"""Production trainer: carousel-fed, checkpointed, resumable, elastic.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 50 --out /tmp/run1 [--resume] [--no-carousel]

The input pipeline is the paper's machinery end to end: a ColdStore corpus
staged by the Stager (with retries + hedged stragglers), transformed
on-demand into packed sequences, and delivered incrementally by the
DeliveryIterator — training starts when the FIRST shard lands.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import Mesh

from repro.carousel.delivery import DeliveryIterator
from repro.carousel.stager import Stager
from repro.carousel.storage import DiskCache
from repro.carousel.transform import make_packing_transform
from repro.ckpt import AsyncCheckpointer, latest_step, load_checkpoint
from repro.configs.base import (RunConfig, ShapeConfig, get_config,
                                get_smoke_config)
from repro.data.synthetic import build_cold_store
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.sharding import ShardingRules, batch_shardings, use_rules
from repro.train.step import init_state, make_train_step, state_shardings


def make_carousel_pipeline(cfg, *, seq_len: int, batch_rows: int,
                           n_shards: int = 64, fault_rate: float = 0.02,
                           cache_bytes: int = 1 << 30, coarse: bool = False,
                           tape_latency: float = 0.001, drives: int = 4):
    cold = build_cold_store(
        n_shards=n_shards, docs_per_shard=16, vocab_size=cfg.vocab_size,
        mean_doc_len=seq_len // 2, drives=drives,
        mount_latency=tape_latency, fault_rate=fault_rate)
    cache = DiskCache(cache_bytes)
    names = [f.name for f in cold.files()]
    stager = Stager(cold, cache, workers=4, max_attempts=6, backoff=0.005,
                    transform=make_packing_transform(seq_len))
    stager.submit_all(names)
    delivery = DeliveryIterator(stager, cache, names,
                                batch_rows=batch_rows, coarse=coarse)
    return stager, delivery


def _batch_iter_carousel(cfg, shape, delivery) -> Iterator[Dict[str, Any]]:
    extra = _modality_extras(cfg, shape)
    for b in delivery:
        yield {**b, **extra}


def _modality_extras(cfg, shape) -> Dict[str, Any]:
    B = shape.global_batch
    if cfg.family == "encdec":
        return {"frames": jnp.zeros((B, cfg.encoder_frames, cfg.d_model),
                                    jnp.bfloat16)}
    if cfg.family == "vlm":
        return {"img_embeds": jnp.zeros((B, cfg.num_img_patches,
                                         cfg.d_model), jnp.bfloat16)}
    return {}


def _batch_iter_synth(cfg, shape) -> Iterator[Dict[str, Any]]:
    i = 0
    while True:
        yield registry.synth_inputs(jax.random.PRNGKey(i), cfg, shape,
                                    "train")
        i += 1


def run_training(
    arch: str,
    *,
    smoke: bool = True,
    steps: int = 20,
    seq_len: int = 64,
    global_batch: int = 4,
    out_dir: Optional[str] = None,
    resume: bool = False,
    carousel: bool = True,
    coarse: bool = False,
    ckpt_every: int = 10,
    tape_latency: float = 0.001,
    drives: int = 4,
    run: Optional[RunConfig] = None,
    on_step: Optional[Callable[[int, Dict[str, float]], None]] = None,
    mesh: Optional[Mesh] = None,
) -> Dict[str, Any]:
    """``mesh``: a ("data", "model") mesh; default: every device of the
    default backend on the data axis.  State is sharded by the param rules
    (FSDP on "embed", TP on the model axis) and each batch on "batch"."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shape = ShapeConfig("train", seq_len, global_batch, "train")
    run = run or RunConfig(total_steps=max(steps, 10), warmup_steps=2,
                           ce_block_v=max(64, cfg.vocab_size // 8))

    rules = ShardingRules(mesh or make_host_mesh())
    st_sh = state_shardings(cfg, rules)
    step_fn = jax.jit(make_train_step(cfg, run), out_shardings=(st_sh, None),
                      donate_argnums=(0,))

    start_step = 0
    if resume and out_dir and latest_step(out_dir) is not None:
        state, meta = load_checkpoint(out_dir, shardings=st_sh)
        start_step = int(meta["step"])
    else:
        state = init_state(jax.random.PRNGKey(0), cfg, run, st_sh)

    ckpt = AsyncCheckpointer(out_dir, keep=3) if out_dir else None
    stager = None
    if carousel:
        # about 8 packed rows per shard: stage twice the rows the run uses
        stager, delivery = make_carousel_pipeline(
            cfg, seq_len=seq_len, batch_rows=global_batch,
            n_shards=max(8, -(-steps * global_batch // 4)), coarse=coarse,
            tape_latency=tape_latency, drives=drives)
        batches = _batch_iter_carousel(cfg, shape, delivery)
    else:
        batches = _batch_iter_synth(cfg, shape)

    losses: List[float] = []
    step_s: List[float] = []
    t0 = time.perf_counter()
    ttfb = None
    batches = iter(batches)
    with use_rules(rules):
        done = start_step
        while True:
            with StepTraceAnnotation("train", step_num=done):
                with TraceAnnotation("train.next_batch"):
                    batch = next(batches, None)
                if batch is None or done >= start_step + steps:
                    break
                t_step = time.perf_counter()
                with TraceAnnotation("train.device_put"):
                    batch = jax.device_put(batch,
                                           batch_shardings(rules, batch))
                with TraceAnnotation("train.dispatch"):
                    state, metrics = step_fn(state, batch)
                with TraceAnnotation("train.loss_read"):
                    loss = float(metrics["loss"])
                step_s.append(time.perf_counter() - t_step)
                if ttfb is None:
                    ttfb = time.perf_counter() - t0
                losses.append(loss)
                done += 1
                if on_step:
                    with TraceAnnotation("train.on_step"):
                        on_step(done, {"loss": loss})
                if ckpt and done % ckpt_every == 0:
                    with TraceAnnotation("train.checkpoint"):
                        ckpt.save(state, done,
                                  meta={"loss": loss, "arch": arch})
    if ckpt:
        with TraceAnnotation("train.checkpoint"):
            ckpt.save(state, done, meta={
                "loss": losses[-1] if losses else None, "arch": arch})
            ckpt.close()
    if stager:
        stager.shutdown()
    return {
        "arch": arch,
        "steps": len(losses),
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "losses": losses,
        "step_s": step_s,
        "time_to_first_batch_s": ttfb,
        "wall_s": time.perf_counter() - t0,
        "final_step": done,
        "state": state,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--out")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-carousel", dest="carousel", action="store_false")
    ap.add_argument("--coarse", action="store_true",
                    help="pre-iDDS baseline: wait for the whole dataset")
    args = ap.parse_args(argv)
    use_compile_cache()
    res = run_training(args.arch, smoke=args.smoke, steps=args.steps,
                       seq_len=args.seq_len, global_batch=args.global_batch,
                       out_dir=args.out, resume=args.resume,
                       carousel=args.carousel, coarse=args.coarse)
    res.pop("state")
    res.pop("losses")
    res.pop("step_s")
    print(res)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
