"""Run one cell of the benchmark once and print its result.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's end-to-end metrics (``--trace 0``) or its per-layer metrics
(``--trace 1``, with the device's busy time and a breakdown read from a
profiler trace of the window) go into one JSON object on the last line
of stdout, with whether the timed path's output matched the plain
reference (``correct``) and each number compared beside its limit; the
same numbers end stderr.  Exits non-zero, printing no result, where JAX
finds no accelerator or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    from chipbench import harness as H
    cell = H.resolve(a.workload)
    device = H.device_info(cell.workload["chips"])
    H.use_compile_cache()
    counter = H.CompileCounter()
    run = H.Run(cell, a.seed, a.seconds, bool(a.trace), t0=T0)
    H.driver(cell.traffic).run(run, counter)
    print(f"window: compilations={run.window_compiles} "
          f"window_s={run.window_s} setup_s={run.setup_s}",
          file=sys.stderr)
    result = H.result_line(run, device, H.metric_values(run))
    for c in run.checks:
        print(f"check: {c['name']}={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
