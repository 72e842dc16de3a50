"""From a ``jax.profiler`` trace to the numbers the benchmark reports.

The reduction works on plain event lists ``(name, start_ns, end_ns)``:

* the window is the host span named ``window``;
* device busy time is the union of the operations' intervals on each
  device's ``XLA Ops`` line, clipped to the window, averaged over devices;
* time per device operation is the sum of its clipped durations (loops
  and calls, whose events hold their bodies' operations, left out);
* idle gaps are the stretches of the window not covered by any operation,
  each put under the innermost harness span open on the host at its middle
  (``other`` where none is);
* programs (the ``XLA Modules`` line) give the device time and the number
  of executions of each jitted program inside the window.
"""
from __future__ import annotations

import collections
import glob
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]   # name, start_ns, end_ns
# ops whose event spans the ops of their body: counted in busy time, not
# in the time per operation
CONTAINERS = ("while", "conditional", "call")


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_ns(ops: Sequence[Event]) -> float:
    return sum(e - s for s, e in merge((s, e) for _, s, e in ops))


def idle_gaps(ops: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for s, e in merge((s, e) for _, s, e in ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_at(t: float, spans: Sequence[Event]) -> str:
    """Innermost (latest-starting) span open at time ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t < sp[2] and (best is None or sp[1] >= best[1]):
            best = sp
    return best[0] if best else "other"


def reduce_events(window: Tuple[float, float],
                  device_ops: Sequence[Sequence[Event]],
                  device_modules: Sequence[Sequence[Event]],
                  host_spans: Sequence[Event], top: int = 10) -> Dict:
    """``device_ops``/``device_modules``: one event list per device."""
    lo, hi = window
    ops = [clip(d, lo, hi) for d in device_ops]
    n_dev = max(len(ops), 1)
    busy = sum(busy_ns(d) for d in ops) / n_dev
    per_op: Dict[str, float] = collections.defaultdict(float)
    for d in ops:
        for name, s, e in d:
            if not name.startswith(CONTAINERS):
                per_op[name] += (e - s) / n_dev
    gap_by: List[Tuple[str, float]] = []
    for d in ops[:1]:   # the first device's gaps stand for all
        for s, e in idle_gaps(d, lo, hi):
            gap_by.append((label_at((s + e) / 2, host_spans), e - s))
    programs: Dict[str, Dict[str, float]] = {}
    for d in device_modules:
        for name, s, e in d:
            if lo <= (s + e) / 2 < hi:
                p = programs.setdefault(name, {"count": 0, "device_s": 0.0})
                p["count"] += 1 / n_dev
                p["device_s"] += (e - s) / n_dev / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / 1e9] for n, t in sorted(
            gap_by, key=lambda kv: -kv[1])[:top]],
        "programs": programs,
    }


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[8,128]``: the op and its result's type, no layout."""
    name, _, rest = hlo.partition(" = ")
    m = re.match(r"(.*?) [\w.-]+\(", re.sub(r"\{[^}]*\}", "", rest))
    return f"{name.lstrip('%')} {m.group(1) if m else ''}"[:96].rstrip()


def events_from_profile(pd, span_names: Sequence[str]):
    """Window, per-device op and module events, and harness host spans
    from a ``jax.profiler.ProfileData``."""
    window = None
    spans: List[Event] = []
    dev_ops: List[List[Event]] = []
    dev_mods: List[List[Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(op_name(e.name), e.start_ns,
                            e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            if ops:
                dev_ops.append(ops)
                dev_mods.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == "window":
                        window = ev[1:]
                    elif e.name in span_names:
                        spans.append(ev)
    if window is None:
        raise RuntimeError("trace: no host span named 'window'")
    if not dev_ops:
        raise RuntimeError("trace: no device operations")
    return window, dev_ops, dev_mods, spans


def reduce_dir(trace_dir: Path, span_names: Sequence[str],
               chips: int) -> Dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"trace: no xplane file under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    window, ops, mods, spans = events_from_profile(pd, span_names)
    out = reduce_events(window, ops[:chips], mods[:chips], spans)
    for name, p in sorted(out["programs"].items(),
                          key=lambda kv: -kv[1]["device_s"])[:8]:
        print(f"trace: program {name} count={p['count']} "
              f"device_s={p['device_s']}", file=sys.stderr)
    return out
