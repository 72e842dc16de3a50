"""What every cell shares: finding its files by name, the device check,
the compile cache, counting compilations, the measured window, tracing,
and the result line.

A cell names a configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``, whose ``driver`` names the general generator
in ``drivers/``) and its limits (``limits/<workload>.json``); each
per-layer metric is read by ``metrics/<metric>.py``.  Adding any of them
takes new files and new entries in BENCHMARK.json, not edits.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


@dataclass
class Cell:
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict[str, Any], workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def resolve(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and the
    metrics it reports; raises KeyError/FileNotFoundError when a name does
    not resolve."""
    bench = bench or benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{wl['traffic']}.json")
    limits = load_json(BENCH_DIR / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(wl, config, traffic, limits, e2e, layer)


def driver(traffic: Dict[str, Any]):
    return importlib.import_module(f"chipbench.drivers.{traffic['driver']}")


def metric_reader(name: str) -> Callable[["Run"], Optional[float]]:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(cfg_json: Dict[str, Any]):
    """The program's configuration as the file states it: the program's
    registered ``program_arch`` with the file's model values, registered
    in the program's config registry as ``chipbench.<name>`` so that its
    entry points run it.  Each value that differs from the registration
    is printed; a key the program does not have is an error."""
    from repro.configs import base as B
    cfg = B.get_config(cfg_json["program_arch"])
    model = {k: v for k, v in cfg_json["model"].items() if k != "param_dtype"}
    unknown = sorted(k for k in model if not hasattr(cfg, k))
    if unknown:
        raise RuntimeError(f"the program's configuration has no {unknown}")
    for k, v in model.items():
        if getattr(cfg, k) != v:
            print(f"config: {k}={v!r} (the program registers "
                  f"{getattr(cfg, k)!r} for {cfg.name})", file=sys.stderr)
    cfg = cfg.replace(name=f"chipbench.{cfg_json['name']}", **model)
    B.register(cfg, cfg)
    return cfg


# --------------------------------------------------------------------------
# device, compile cache, compilations
# --------------------------------------------------------------------------


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache/`` at the root of this checkout (a fixed path: the
    path is part of the cache key).  Every program is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> Dict[str, Any]:
    """The accelerator as JAX reports it; exits non-zero without printing
    a result where there is none or too few."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu":
        sys.exit("chipbench: JAX found no accelerator")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, "
                 f"JAX found {len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak(kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of ``kind`` (``peaks.json``); a kind
    not in the table is an error."""
    kinds = load_json(BENCH_DIR / "peaks.json")["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peaks for device kind {kind!r}")
    return kinds[kind]


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts, while ``counting`` is set, programs compiled by the backend
    and programs loaded from the persistent cache."""

    COMPILED = "/jax/core/compile/backend_compile_duration"
    LOADED = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _s, **_kw: self._on(name))
        jax.monitoring.register_event_listener(
            lambda name, **_kw: self._on(name))

    def _on(self, name: str) -> None:
        if self.counting and name in (self.COMPILED, self.LOADED):
            self.count += 1


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


@dataclass
class Run:
    """What a driver hands back and the metric readers read."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float = field(default_factory=time.perf_counter)
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    counters: Dict[str, Any] = field(default_factory=dict)
    trace_summary: Optional[Dict[str, Any]] = None
    checks: List[Dict[str, Any]] = field(default_factory=list)
    memory_peak_bytes: Optional[int] = None
    window_compiles: int = 0
    # what the checks compared, for the control's readings
    compared: Dict[str, Any] = field(default_factory=dict)

    def check(self, name: str, value: float, limit: float) -> None:
        """A number compared with its limit: correct while value <= limit."""
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": bool(value <= limit)})

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)


class Tracer:
    """The profiler around the measured window, into a fixed directory
    inside the checkout (emptied first)."""

    def __init__(self, run: Run):
        self.on = run.trace
        self.dir = BENCH_DIR / ".traces" / run.cell.workload["name"]
        self._window = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self._window = jax.profiler.TraceAnnotation("window")
        self._window.__enter__()

    def end_window(self) -> None:
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self, run: Run, span_names) -> None:
        if not self.on:
            return
        import jax
        self.end_window()
        jax.profiler.stop_trace()
        from chipbench import trace
        t = now()
        run.trace_summary = trace.reduce_dir(self.dir, span_names,
                                             run.cell.workload["chips"])
        shutil.rmtree(self.dir, ignore_errors=True)
        print(f"trace: reduced in {now() - t} s", file=sys.stderr)


class GcPauses:
    """At the window's opening every object made in set-up is moved out
    of the collector's reach (``gc.freeze``), so that a full collection
    inside the window scans only what the window made; the collections
    that run in the window are recorded."""

    def __init__(self):
        self.pauses: List[tuple] = []     # (generation, seconds)
        self.on = False
        self._t: Optional[float] = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict[str, Any]) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t = now()
        elif self._t is not None:
            self.pauses.append((info["generation"], now() - self._t))
            self._t = None

    def open(self) -> None:
        gc.freeze()
        self.on = True

    def close(self) -> None:
        self.on = False

    def summary(self) -> str:
        full = [s for g, s in self.pauses if g == 2]
        return (f"gc: window collections={len(self.pauses)} full={len(full)} "
                f"longest_s={max((s for _, s in self.pauses), default=0.0)}")


def span(name: str, on: bool):
    """A host span in the profiler's trace when tracing, else nothing."""
    if on:
        import jax
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


def now() -> float:
    return time.perf_counter()


def metric_values(run: Run) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics, or with tracing its per-layer ones;
    a metric whose reader finds nothing is left out."""
    out = {}
    for m in run.cell.per_layer if run.trace else run.cell.end_to_end:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(run: Run, device: Dict[str, Any],
                metrics: Dict[str, Any]) -> Dict[str, Any]:
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    out: Dict[str, Any] = {"correct": run.correct, "attempted": run.attempted,
                           "failed": run.failed, "metrics": metrics,
                           "device": dev}
    if run.trace and run.trace_summary:
        ts = run.trace_summary
        dev["busy_s"] = ts["busy_s"]
        dev["window_s"] = ts["window_s"]
        out["breakdown"] = {"device_ops": ts["device_ops"],
                            "idle_gaps": ts["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in run.checks}
    return out
