"""Readings that the limits of ``limits/<workload>.json`` are set from.

    python3 chipbench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>] \
        [--doc-lengths-seed <n>]

For each seed, in this one process: a run of the cell as the benchmark
makes it (the program's readings: what sound runs give), then, on the
first ``k`` seeds (all by default), the control on the same inputs: the
reference put in the program's place, with every matrix product's
operands rounded to the precision below the one the configuration states
(float8 e4m3 for bfloat16), and the fault "half of the batch left out,
the mean taken over the rest", planted in the reference.
``--doc-lengths-seed`` reads them under another draw of the corpus's
document lengths than the cell's.  One JSON line per seed.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# the precision below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn"}


def train_readings(r) -> dict:
    from chipbench.drivers import train_carousel as D
    from chipbench.reference import mamba2 as ref_model
    from chipbench.reference.numerics import Numerics
    conf = r.cell.config
    m = conf["model"]
    spec = ref_model.param_spec(m)
    c = r.compared
    nx = Numerics(LOWER[m["param_dtype"]])
    control = D.reference_readings(spec, m, conf, r.seed, c["batches"], nx)
    half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
            for b in c["batches"]]
    fault = D.reference_readings(spec, m, conf, r.seed, half)
    return {"control": D.gaps(control, c["reference"]),
            "half_batch": D.gaps(fault, c["reference"]),
            "detail": {"program": c["program"], "reference": c["reference"],
                       "control": control, "half_batch": fault}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int)
    ap.add_argument("--doc-lengths-seed", type=int)
    ap.add_argument("--detail", help="append each seed's per-step losses "
                    "and per-leaf norms to this file")
    a = ap.parse_args(argv)
    from chipbench import harness as H
    cell = H.resolve(a.workload)
    if a.doc_lengths_seed is not None:
        cell.traffic = dict(cell.traffic, doc_lengths_seed=a.doc_lengths_seed)
    H.device_info(cell.workload["chips"])
    H.use_compile_cache()
    counter = H.CompileCounter()
    n_control = len(a.seeds) if a.control_seeds is None else a.control_seeds
    for i, seed in enumerate(a.seeds):
        r = H.Run(cell, seed, a.seconds, False)
        H.driver(cell.traffic).run(r, counter)
        out = {"seed": seed, "correct": r.correct,
               "doc_lengths_seed": cell.traffic["doc_lengths_seed"],
               "program": {c["name"]: c["value"] for c in r.checks}}
        if i < n_control:
            out.update(train_readings(r))
        detail = out.pop("detail", None) or {
            k: r.compared[k] for k in ("program", "reference")}
        print(json.dumps(out), flush=True)
        if a.detail and detail:
            with open(a.detail, "a") as f:
                f.write(json.dumps({"seed": seed, **detail}) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
