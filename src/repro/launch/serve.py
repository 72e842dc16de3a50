"""Batched serving driver: prefill a batch of prompts, decode greedily.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --prompt-len 32 --gen 16 --batch 4
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (RunConfig, ShapeConfig, get_config,
                                get_smoke_config)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.serve import engine
from repro.sharding import ShardingRules, use_rules
from repro.train.step import init_state


def run_serving(arch: str, *, smoke: bool = True, prompt_len: int = 32,
                gen: int = 16, batch: int = 4,
                run: Optional[RunConfig] = None) -> Dict[str, Any]:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    run = run or RunConfig()
    mesh = make_host_mesh()
    rules = ShardingRules(mesh)

    shape = ShapeConfig("serve", prompt_len, batch, "prefill")
    prompts = registry.synth_inputs(jax.random.PRNGKey(0), cfg, shape,
                                    "prefill")
    extra = cfg.num_img_patches if cfg.family == "vlm" else 0
    max_len = prompt_len + extra + gen + 8

    prefill = jax.jit(engine.make_prefill_step(cfg, run),
                      donate_argnums=(2,))
    decode = jax.jit(engine.make_decode_step(cfg, run), donate_argnums=(2,))

    with use_rules(rules):
        params = init_state(jax.random.PRNGKey(1), cfg, run)["params"]
        cache = engine.init_cache(cfg, batch, max_len)
        t0 = time.time()
        tok, cache = prefill(params, prompts, cache)
        tok.block_until_ready()
        t_prefill = time.time() - t0
        out_tokens = [tok]
        pos = prompt_len + extra
        t1 = time.time()
        for i in range(gen - 1):
            tok, cache = decode(params, tok, cache,
                                jnp.asarray(pos + i, jnp.int32))
            out_tokens.append(tok)
        jax.block_until_ready(out_tokens[-1])
        t_decode = time.time() - t1
    seq = jnp.concatenate(out_tokens, axis=1)
    return {
        "arch": arch,
        "generated": seq.shape,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tok_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
        "tokens": seq,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)
    use_compile_cache()
    res = run_serving(args.arch, smoke=args.smoke,
                      prompt_len=args.prompt_len, gen=args.gen,
                      batch=args.batch)
    res.pop("tokens")
    print(res)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
