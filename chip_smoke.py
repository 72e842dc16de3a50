#!/usr/bin/env python3
"""Bring-up check: the main path, end to end, on TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # one host with four chips

One chip: mamba2-130m at its published widths (24 layers, d_model 768,
vocab 50280, seq 2048, batch 8) trains for 8 steps fed by the Data
Carousel (stager, packing transform, delivery iterator), checkpointing
asynchronously, then resumes for 2 more; the serving driver prefills 512
tokens and decodes 32 for 8 prompts; each Pallas kernel, compiled for the
chip, is compared with its pure-jnp reference.

Four chips: zamba2-1.2b on the Zamba2 hybrid block (some of its sizes
unverified, see ``configs/zamba2_1_2b.py``), whose training state
(14.2 GB) does not fit one chip, trains 3 steps on a (4, 1) and on a
(2, 2) ("data", "model") mesh; the first-step losses must agree.

Weights and data come from fixed seeds.  Everything runs in this one
process, which holds the chips.  Every phase raises on a failed check.
The last line of stdout is one JSON object naming the device, printed
only when every phase passed; without a TPU the script exits non-zero
before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def device_phase(count: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {d.platform!r}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if len(devs) != count:
        sys.exit(f"chip_smoke: this phase needs {count} chips, "
                 f"found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def train_phase() -> None:
    from repro.launch.train import run_training
    ln_v = math.log(50280)
    with tempfile.TemporaryDirectory() as out:
        kw = dict(smoke=False, seq_len=2048, global_batch=8, carousel=True,
                  out_dir=out, ckpt_every=4)
        first = run_training("mamba2-130m", steps=8, **kw)
        first.pop("state")
        resumed = run_training("mamba2-130m", steps=2, resume=True, **kw)
        resumed.pop("state")
    losses = first["losses"] + resumed["losses"]
    print(f"train: first_step_s={first['step_s'][0]} (compile included) "
          f"median_later_step_s={statistics.median(first['step_s'][1:])} "
          f"time_to_first_batch_s={first['time_to_first_batch_s']}",
          flush=True)
    print(f"train: losses={first['losses']}", flush=True)
    print(f"resume: first_step_s={resumed['step_s'][0]} "
          f"final_step={resumed['final_step']} "
          f"losses={resumed['losses']}", flush=True)
    check(first["steps"] == 8 and resumed["steps"] == 2, "step counts")
    check(all(math.isfinite(x) for x in losses), "finite losses")
    check(abs(losses[0] - ln_v) <= 1.0,
          f"first loss {losses[0]} within 1.0 of ln(50280)={ln_v}")
    check(resumed["final_step"] == 10, "final_step 10 after resume")


def serve_phase() -> None:
    import numpy as np
    from repro.launch.serve import run_serving
    res = run_serving("mamba2-130m", smoke=False, prompt_len=512, gen=32,
                      batch=8)
    toks = np.asarray(res["tokens"])
    print(f"serve: prefill_s={res['prefill_s']} decode_s={res['decode_s']} "
          f"(first call of each compiles) tokens_shape={toks.shape}",
          flush=True)
    check(toks.shape == (8, 32), f"token shape {toks.shape}")
    check(toks.min() >= 0 and toks.max() < 50280, "token ids in [0, 50280)")


def kernel_phase() -> None:
    """Each kernel compiled by Mosaic (interpret=False) against its
    reference, at mamba2-130m's widths (flash attention at a 20-head,
    128-dim, 4096-token shape).  Inputs are bf16; the references run at
    "highest" matmul precision.  A kernel passes when every element obeys
    |kernel - ref| <= tol * (1 + |ref|), with tol the bf16 tolerance of
    tests/test_kernels.py (2e-2; 3e-2 for the SSD scan)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.cross_entropy import cross_entropy_pallas
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.kernels.ssd_scan import ssd_pallas

    bf = jnp.bfloat16
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    normal = lambda i, shape, s=1.0: (jax.random.normal(
        k[i], shape, jnp.float32) * s).astype(bf)

    x = normal(0, (16384, 768))
    w = normal(1, (768,))
    q, kk, v = (normal(i, (1, 4096, 20, 128)) for i in (2, 3, 4))
    xs = normal(0, (8, 2048, 24, 64), 0.5)
    dt = jax.nn.softplus(jax.random.normal(k[5], (8, 2048, 24)))
    A = -jnp.exp(jax.random.normal(k[6], (24,)) * 0.3)
    Bm, Cm = normal(2, (8, 2048, 1, 128), 0.3), normal(3, (8, 2048, 1, 128),
                                                        0.3)
    wv = normal(4, (50280, 768), 0.05)
    tg = jax.random.randint(k[7], (16384,), 0, 50280, jnp.int32)

    fa = lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False)
    cases = [
        ("rmsnorm", 2e-2, lambda x, w: rmsnorm_pallas(x, w, interpret=False),
         ref.rmsnorm_ref, (x, w)),
        ("flash_attention", 2e-2, fa, ref.flash_attention_ref, (q, kk, v)),
        ("ssd", 3e-2,
         lambda *a: ssd_pallas(*a, chunk=128, interpret=False),
         lambda *a: ref.ssd_ref(*a, chunk=128), (xs, dt, A, Bm, Cm)),
        ("cross_entropy", 2e-2,
         lambda *a: cross_entropy_pallas(*a, interpret=False),
         ref.cross_entropy_direct_ref, (x, wv, tg)),
    ]
    for name, tol, kern, oracle, args in cases:
        a = np.asarray(jax.jit(kern)(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            b = np.asarray(jax.jit(oracle)(*args), np.float32)
        err = np.abs(a - b)
        excess = float(np.max(err - tol * (1.0 + np.abs(b))))
        print(f"kernel: {name} max_abs_err={float(err.max())} "
              f"max_abs_ref={float(np.abs(b).max())} tol={tol} "
              f"worst_margin={excess}", flush=True)
        check(bool(np.isfinite(a).all()), f"{name}: finite output")
        check(excess <= 0.0, f"{name}: within tolerance {tol}")


def four_chip_phase() -> None:
    """zamba2-1.2b training sharded over four chips: FSDP on "embed" over
    a (4, 1) mesh, then FSDP x TP over (2, 2), on the same synthetic
    batches (seed 0), so the two first-step losses must agree."""
    import jax
    from repro.configs.base import RunConfig, get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import run_training
    from repro.models import params as P
    from repro.models import registry
    from repro.sharding import ShardingRules
    from repro.train.step import init_state, state_shardings

    arch = "zamba2-1.2b"
    cfg = get_config(arch)
    defs = registry.param_defs(cfg)
    # bf16 params and grads, f32 first and second moments
    state_bytes = 2 * P.param_bytes(defs) + 8 * P.param_count(defs)
    ln_v = math.log(cfg.vocab_size)
    first = {}
    for shape in [(4, 1), (2, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        state = init_state(jax.random.PRNGKey(0), cfg, RunConfig(),
                           state_shardings(cfg, ShardingRules(mesh)))
        jax.block_until_ready(state)
        used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        del state
        res = run_training(arch, smoke=False, steps=3, seq_len=2048,
                           global_batch=8, carousel=False, mesh=mesh)
        res.pop("state")
        first[shape] = res["losses"][0]
        print(f"four_chips: mesh={shape} state_bytes={state_bytes} "
              f"bytes_in_use_after_init={used} "
              f"max_share_of_state={max(used) / state_bytes}", flush=True)
        print(f"four_chips: mesh={shape} first_step_s={res['step_s'][0]} "
              f"(compile included) later_step_s={res['step_s'][1:]} "
              f"losses={res['losses']}", flush=True)
        check(max(used) <= 0.4 * state_bytes,
              f"{shape}: no device holds over 40% of the state")
        check(all(math.isfinite(x) for x in res["losses"]),
              f"{shape}: finite losses")
        check(abs(res["losses"][0] - ln_v) <= 1.0,
              f"{shape}: first loss within 1.0 of ln(32000)={ln_v}")
    gap = abs(first[(4, 1)] - first[(2, 2)])
    print(f"four_chips: first_step_loss (4, 1)={first[(4, 1)]} "
          f"(2, 2)={first[(2, 2)]} abs_diff={gap}", flush=True)
    check(gap <= 2e-2, "first-step losses of the two meshes within 2e-2")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded zamba2-1.2b phase on 4 chips")
    args = ap.parse_args(argv)

    # the program first: without it, fail before touching the chip
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    device = device_phase(4 if args.four_chips else 1)
    print(f"compile_cache: {use_compile_cache()}", flush=True)
    if args.four_chips:
        four_chip_phase()
    else:
        train_phase()
        serve_phase()
        kernel_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
