"""Readings that the limits of a cell driven by ``train_carousel_sharded``
are set from, with the run's own metrics, for several seeds in one
process (one compilation of each program).

    python3 chipbench/control_sharded.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>] [--trace-first] \
        [--faults control half_batch no_exchange]

For each seed: a run of the cell as the benchmark makes it (its checks and
every metric its readers find), then, on the first ``k`` seeds (all by
default), the reference trained sharded over the cell's mesh on the same
batches with one of ``FAULTS`` planted, each read against the sound
reference as the run's own output is.  ``--trace-first`` traces the first
seed's window.  Everything runs in order on one thread: the reference's
programs compile after the window, as in the benchmark's own runs.  One
JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness as H  # noqa: E402

# control: every product's operands rounded to the precision below the
# configuration's; half_batch: half of each batch left out; no_exchange:
# the first chip's rows alone, which is what that chip trains on when the
# gradients are not exchanged between the chips
FAULTS = ("control", "half_batch", "no_exchange")


def planted(fault: str, batches, m, mesh):
    """The batches and numerics of the reference with ``fault`` planted."""
    from chipbench.control import LOWER
    from chipbench.reference.numerics import Numerics
    if fault == "control":
        return batches, Numerics(LOWER[m["param_dtype"]])
    part = 2 if fault == "half_batch" else mesh.shape["data"]
    return [{k: v[: v.shape[0] // part] for k, v in b.items()}
            for b in batches], None


def readings(r, faults=FAULTS) -> dict:
    from chipbench.drivers import train_carousel_sharded as D
    conf = r.cell.config
    m = conf["model"]
    spec = D.ref_model.param_spec(m)
    c = r.compared
    out = {"losses": {"program": c["program"]["losses"],
                      "reference": c["reference"]["losses"]}}
    for fault in faults:
        batches, nx = planted(fault, c["batches"], m, c["mesh"])
        got = D.reference_readings(spec, m, conf, r.seed, batches,
                                   c["mesh"], nx)
        out[fault] = D.gaps(got, c["reference"])
        out["losses"][fault] = got["losses"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int)
    ap.add_argument("--trace-first", action="store_true")
    ap.add_argument("--faults", nargs="+", choices=FAULTS, default=FAULTS)
    a = ap.parse_args(argv)
    cell = H.resolve(a.workload)
    device = H.device_info(cell.workload["chips"])
    H.use_compile_cache()
    counter = H.CompileCounter()
    n_control = len(a.seeds) if a.control_seeds is None else a.control_seeds
    for i, seed in enumerate(a.seeds):
        r = H.Run(cell, seed, a.seconds, a.trace_first and i == 0)
        H.driver(cell.traffic).run(r, counter)
        metrics = {}
        for m in cell.end_to_end + (cell.per_layer if r.trace else []):
            v = H.metric_reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = v
        out = {"seed": seed, "correct": r.correct, "traced": r.trace,
               "metrics": metrics,
               "checks": {c["name"]: c["value"] for c in r.checks},
               "memory_peak_bytes": r.memory_peak_bytes, "device": device}
        if r.trace:
            out["busy_s"] = r.trace_summary["busy_s"]
            out["window_s"] = r.trace_summary["window_s"]
        if i < n_control:
            out.update(readings(r, a.faults))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
