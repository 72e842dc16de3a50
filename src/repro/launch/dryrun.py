"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, and fits — and extract the roofline terms from the compiled
artifact.  MUST be executed as its own process (the XLA_FLAGS lines below
run before any jax import).

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs.base import (RunConfig, SHAPES, all_cells, cell_is_runnable,
                                get_config)
from repro.launch import hlo_cost
from repro.launch import mesh as mesh_lib
from repro.models import params as P
from repro.models import registry
from repro.serve import engine
from repro.sharding import (ShardingRules, batch_shardings, param_shardings,
                            use_rules)
from repro.train import step as train_step_lib


def host_production_mesh(multi_pod: bool):
    """Production mesh over the forced host devices, never the default
    backend: on a TPU machine that backend has only the attached chips."""
    return mesh_lib.make_production_mesh(multi_pod=multi_pod,
                                         devices=jax.devices("cpu"))


def cache_shardings(cfg, rules: ShardingRules, batch: int, max_len: int):
    defs = engine.cache_defs(cfg, batch, max_len)
    return P.tree_map(lambda d: rules.sharding(d.logical, d.shape), defs)


def default_run_config(arch: str, shape_name: str,
                       overrides: Optional[Dict[str, Any]] = None,
                       ) -> RunConfig:
    run = RunConfig()
    if (arch, shape_name) == ("zamba2-1.2b", "long_500k"):
        # XLA CPU segfaults compiling the scanned variant of this one
        # program (hybrid decode w/ 500k KV); the unrolled build compiles
        # and yields identical roofline terms. 38 layers unroll cheaply.
        run = run.replace(scan_layers=False)
    if overrides:
        run = run.replace(**overrides)
    return run


def lower_cell(arch: str, shape_name: str, mesh, *,
               run_overrides: Optional[Dict[str, Any]] = None):
    """Build + lower one cell. Returns (lowered, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    run = default_run_config(arch, shape_name, run_overrides)
    rules = ShardingRules(mesh)
    specs = registry.input_specs(cfg, shape)

    with use_rules(rules):
        if shape.kind == "train":
            state_abs = train_step_lib.abstract_state(cfg, run)
            st_sh = train_step_lib.state_shardings(cfg, rules)
            b_sh = batch_shardings(rules, specs)
            fn = train_step_lib.make_train_step(cfg, run)
            lowered = jax.jit(
                fn, in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
            ).lower(state_abs, specs)
        elif shape.kind == "prefill":
            params_abs = P.abstract(registry.param_defs(cfg))
            defs = registry.param_defs(cfg)
            p_sh = param_shardings(defs, rules)
            # vlm prefill writes img_patches + text tokens into the cache
            max_len = shape.seq_len + cfg.num_img_patches + 8
            cache_abs = engine.abstract_cache(cfg, shape.global_batch,
                                              max_len)
            c_sh = cache_shardings(cfg, rules, shape.global_batch, max_len)
            b_sh = batch_shardings(rules, specs)
            fn = engine.make_prefill_step(cfg, run)
            lowered = jax.jit(
                fn, in_shardings=(p_sh, b_sh, c_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(2,),
            ).lower(params_abs, specs, cache_abs)
        else:  # decode
            params_abs = P.abstract(registry.param_defs(cfg))
            defs = registry.param_defs(cfg)
            p_sh = param_shardings(defs, rules)
            cache_abs = engine.abstract_cache(cfg, shape.global_batch,
                                              shape.seq_len)
            c_sh = cache_shardings(cfg, rules, shape.global_batch,
                                   shape.seq_len)
            tok_sh = rules.sharding(("batch", None), (shape.global_batch, 1))
            fn = engine.make_decode_step(cfg, run)
            lowered = jax.jit(
                fn, in_shardings=(p_sh, tok_sh, c_sh, None),
                out_shardings=(tok_sh, c_sh),
                donate_argnums=(2,),
            ).lower(params_abs, specs["tokens"], cache_abs, specs["pos"])

    n_params = P.param_count(registry.param_defs(cfg))
    return lowered, {"arch": arch, "shape": shape_name, "kind": shape.kind,
                     "n_params": n_params}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             mesh=None, run_overrides: Optional[Dict[str, Any]] = None,
             collect_hlo: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}
    t0 = time.time()
    if mesh is None:
        mesh = host_production_mesh(multi_pod)
    n_chips = mesh.devices.size
    try:
        lowered, meta = lower_cell(arch, shape_name, mesh,
                                   run_overrides=run_overrides)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # our walker: per-device flops/bytes with while-loop trip counts
        # (XLA's cost_analysis counts loop bodies once — see hlo_cost.py)
        walk = hlo_cost.analyze(compiled.as_text()) if collect_hlo else {}
        out = {
            **meta,
            "status": "ok",
            "mesh": (f"{'pod2x' if multi_pod else ''}"
                     f"{tuple(mesh.shape.values())}"),
            "chips": n_chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "hlo_flops": walk.get("flops", 0.0),           # per device
            "hlo_bytes": walk.get("hbm_bytes", 0.0),       # per device
            "collective_bytes": {
                k.replace("coll_", ""): v for k, v in walk.items()
                if k.startswith("coll_")},
            "collective_total": walk.get("collective_bytes", 0.0),
            "xla_cost_flops": float(cost.get("flops", 0.0)),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            },
        }
        out["model_flops"] = model_flops(cfg, shape)
        out["roofline"] = roofline_terms(out)
        return out
    except Exception as e:
        return {"arch": arch, "shape": shape_name, "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}


def active_params(cfg) -> int:
    """Params touched per token: excludes the input embedding gather; MoE
    counts only the top-k routed experts."""
    defs = registry.param_defs(cfg)
    total = P.param_count(defs)
    emb = int(cfg.vocab_size) * int(cfg.d_model)
    total -= emb  # tok embedding (gather, not matmul)
    if cfg.num_experts and cfg.num_experts_per_tok:
        per_layer_expert = 3 * cfg.d_model * cfg.d_ff  # gate+up+down
        inactive = (cfg.num_experts - cfg.num_experts_per_tok)
        total -= cfg.num_layers * inactive * per_layer_expert
    return int(total)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (fwd-only), N = active params,
    D = tokens processed. Global (all chips)."""
    N = active_params(cfg)
    if shape.kind == "train":
        D = shape.global_batch * shape.seq_len
        return 6.0 * N * D
    if shape.kind == "prefill":
        D = shape.global_batch * shape.seq_len
        return 2.0 * N * D
    return 2.0 * N * shape.global_batch  # decode: one token per sequence


def roofline_terms(cell: Dict[str, Any]) -> Dict[str, Any]:
    chips = cell["chips"]
    flops = cell["hlo_flops"]       # per device (hlo_cost walker)
    byts = cell["hlo_bytes"]        # per device
    coll = cell.get("collective_total", 0.0)  # per device
    t_c = flops / mesh_lib.PEAK_FLOPS_BF16
    t_m = byts / mesh_lib.HBM_BW
    t_n = coll / mesh_lib.ICI_BW
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_n}
    dom = max(terms, key=terms.get)
    bound = max(t_c, t_m, t_n, 1e-30)
    terms["dominant"] = dom
    terms["bound_s"] = bound
    terms["compute_fraction"] = t_c / bound
    mf = cell.get("model_flops", 0.0)
    terms["useful_flops_ratio"] = mf / (flops * chips) if flops else 0.0
    return terms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--run-overrides", help="JSON dict of RunConfig fields")
    args = ap.parse_args(argv)

    overrides = json.loads(args.run_overrides) if args.run_overrides else None
    cells = []
    if args.all:
        cells = [(a, s) for a, s, ok, _ in all_cells()]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch/--shape or --all required")
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    n_bad = 0
    for mp in meshes:
        mesh = host_production_mesh(mp)
        for arch, shape in cells:
            r = run_cell(arch, shape, multi_pod=mp, mesh=mesh,
                         run_overrides=overrides)
            results.append(r)
            status = r["status"]
            line = (f"[{status}] {arch} x {shape} "
                    f"mesh={'2x16x16' if mp else '16x16'}")
            if status == "ok":
                rf = r["roofline"]
                line += (f" flops/dev={r['hlo_flops']:.3e}"
                         f" bytes/dev={r['hlo_bytes']:.3e}"
                         f" coll/dev={r['collective_total']:.3e}"
                         f" dom={rf['dominant'][:-2]}"
                         f" bound={rf['bound_s']*1e3:.1f}ms"
                         f" useful={rf['useful_flops_ratio']:.2f}"
                         f" compile={r['compile_s']}s")
            elif status == "error":
                n_bad += 1
                line += " " + r["error"]
            else:
                line += f" ({r['reason'][:60]})"
            print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 1 if n_bad else 0


if __name__ == "__main__":
    sys.exit(main())
