"""The harness's profiler, with the trace also reduced by named scope
(``scopes.py``) and by collective, over every chip of the cell, before the
trace is deleted: ``trace_summary`` gains ``scopes`` (seconds per scope
and part, averaged over the chips) and ``collectives_s`` (seconds of
device time in collective ops, averaged over the chips: all-gather,
reduce-scatter, all-reduce, all-to-all and collective-permute, which
carries the all-gathers and reduce-scatters that the TPU compiler splits
into steps beside the products that use them; asynchronous start and done
halves included), with ``collective_ops``, the costliest of them; and
``within`` (seconds of every op anywhere inside each scope of ``WITHIN``
that the program has, averaged over the chips: the scopes nested in it
included, which ``scopes`` counts under the innermost name alone)."""
from __future__ import annotations

import collections
import glob
import re
import shutil
import sys
from pathlib import Path

from chipbench import harness as H
from chipbench import scopes, trace

WITHIN = ("shared_block",)
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")


def within(window, device_ops, tf_op, names):
    """Seconds per device of the ops anywhere inside each of ``names``,
    averaged over the devices; a name no op is inside is left out."""
    out = {}
    for name in names:
        parts = scopes.reduce_scopes(window, device_ops, tf_op,
                                     (name,)).get(name)
        if parts:
            out[name] = sum(parts.values())
    return out


def collectives(window, device_ops, top: int = 10):
    """Seconds per device in collective ops within ``window``, averaged
    over the devices, and the ``top`` costliest ops."""
    n_dev = max(len(device_ops), 1)
    per_op = collections.defaultdict(float)
    for d in device_ops:
        for name, s, e in trace.clip(d, *window):
            if COLLECTIVE.search(name.split(" ")[0]):
                per_op[name] += (e - s) / n_dev / 1e9
    return sum(per_op.values()), sorted(per_op.items(),
                                        key=lambda kv: -kv[1])[:top]


class ScopedTracer(H.Tracer):
    def stop(self, run: H.Run, span_names) -> None:
        if not self.on:
            return
        import jax
        from jax.profiler import ProfileData
        self.end_window()
        jax.profiler.stop_trace()
        t = H.now()
        chips = run.cell.workload["chips"]
        files = sorted(glob.glob(str(Path(self.dir) / "plugins" / "profile"
                                     / "*" / "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"trace: no xplane file under {self.dir}")
        window, ops, mods, spans = trace.events_from_profile(
            ProfileData.from_file(files[-1]), span_names)
        out = trace.reduce_events(window, ops[:chips], mods[:chips], spans)
        tf_op = scopes.tf_ops(files[-1])
        names = scopes.program_scopes()
        out["scopes"] = scopes.reduce_scopes(window, ops[:chips], tf_op,
                                             names)
        out["within"] = within(window, ops[:chips], tf_op,
                               [n for n in WITHIN if n in names])
        out["collectives_s"], out["collective_ops"] = collectives(
            window, ops[:chips])
        run.trace_summary = out
        shutil.rmtree(self.dir, ignore_errors=True)
        for name, p in sorted(out["scopes"].items(),
                              key=lambda kv: -sum(kv[1].values())):
            print(f"scope: {name} total_s={sum(p.values())} "
                  + " ".join(f"{k}_s={p[k]}" for k in scopes.PARTS),
                  file=sys.stderr)
        for name, s in out["within"].items():
            print(f"within: {name} total_s={s}", file=sys.stderr)
        for name, s in out["collective_ops"]:
            print(f"collective: {name} s={s}", file=sys.stderr)
        print(f"trace: collectives_s={out['collectives_s']} busy_s="
              f"{out['busy_s']} window_s={out['window_s']} reduced in "
              f"{H.now() - t} s", file=sys.stderr)
