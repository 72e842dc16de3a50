"""Device time per named scope, and idle time per host span, from a
``jax.profiler`` trace.

XLA writes each op's ``op_name`` path, with the ``jax.named_scope``s it
was traced under, into the trace's event metadata as the stat ``tf_op``
(``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/ssd/mul``).
``jax.profiler.ProfileData`` does not expose metadata stats, so
``tf_ops`` reads them from the ``.xplane.pb`` file's protobuf wire format:
it decodes each plane's metadata maps and skips its event lines.

* Each window-clipped device op (loops and calls left out, as in
  ``trace.py``) goes under the innermost name of the program's scope list
  found in its path, or under ``unscoped``; within a scope its time is
  ``recompute`` (``rematted_computation`` in the path), ``bwd``
  (``transpose(``) or ``fwd``.
* The idle time of the window is summed under the innermost host span
  open at each instant (``other`` where none is).

    python3 chipbench/scopes.py <file.xplane.pb>

prints one stderr line per scope and per span label, and the whole
reduction as JSON on stdout.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, Optional, Sequence, Tuple

if __package__ in (None, ""):
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import trace  # noqa: E402

PARTS = ("fwd", "bwd", "recompute")
# host spans that label idle gaps: the harness's own, the training loop's
# and the carousel consumer's
SPANS = ("input", "train.next_batch", "train.device_put", "train.dispatch",
         "train.loss_read", "train.on_step", "train.checkpoint",
         "carousel.shard_wait", "carousel.assemble")


# --------------------------------------------------------------------------
# the .xplane.pb metadata maps
# --------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in ``buf[lo:hi]``: an int for a
    varint, a ``(lo, hi)`` range for a length-delimited field (nothing is
    copied), raw bytes for fixed-width ones."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield num, v


def _text(buf: bytes, rng) -> str:
    return bytes(buf[rng[0]:rng[1]]).decode("utf-8", "replace")


def _map_values(buf: bytes, rng) -> Iterator[Tuple[int, int]]:
    """The value ranges of a protobuf map entry (key 1, value 2)."""
    for num, v in _fields(buf, *rng):
        if num == 2:
            yield v


def tf_ops(path: str) -> Dict[str, str]:
    """Device op name, as ``trace.op_name`` gives it, -> its ``tf_op``
    path, from every plane of the file.  XSpace.planes is field 1; in an
    XPlane, name 2, lines 3 (skipped), event_metadata 4, stat_metadata 5;
    in an XEventMetadata, name 2 and stats 5; in an XStat, metadata_id 1,
    str_value 5 or ref_value 7 (the id of a stat metadata whose name is
    the string)."""
    buf = memoryview(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        events, stat_names = [], {}
        for pnum, v in _fields(buf, *plane):
            if pnum == 4:
                events.append(v)
            elif pnum == 5:
                for md in _map_values(buf, v):
                    f = dict(_fields(buf, *md))
                    if 2 in f:
                        stat_names[f.get(1, 0)] = _text(buf, f[2])
        tf_id = [k for k, n in stat_names.items() if n == "tf_op"]
        if not tf_id:
            continue
        for entry in events:
            for md in _map_values(buf, entry):
                name, op = None, None
                for enum, ev in _fields(buf, *md):
                    if enum == 2:
                        name = _text(buf, ev)
                    elif enum == 5:
                        s = dict(_fields(buf, *ev))
                        if s.get(1) != tf_id[0]:
                            continue
                        if 5 in s:
                            op = _text(buf, s[5])
                        elif 7 in s:
                            op = stat_names.get(s[7])
                if name and op is not None:
                    out.setdefault(trace.op_name(name), op)
    return out


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

_JIT = re.compile(r"\bp?jit\([^()]*\)")


def scope_of(tf_op: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost name of ``scopes`` in the path: its components, with
    transforms unwrapped (``transpose(jvp(logits_ce))``) and jitted
    functions' names (``jit(_take)``) and the op itself left out."""
    names = [t for t in re.split(r"[/()]", _JIT.sub("", tf_op)) if t][:-1]
    found = [t for t in names if t in scopes]
    return found[-1] if found else None


def part_of(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "recompute"
    return "bwd" if "transpose(" in tf_op else "fwd"


def reduce_scopes(window: Tuple[float, float],
                  device_ops: Sequence[Sequence[trace.Event]],
                  tf_op: Dict[str, str], scopes: Sequence[str]
                  ) -> Dict[str, Dict[str, float]]:
    """Seconds per scope (and ``unscoped``) and part, averaged over
    devices; ``device_ops`` as ``trace.events_from_profile`` gives them."""
    lo, hi = window
    ops = [trace.clip(d, lo, hi) for d in device_ops]
    n_dev = max(len(ops), 1)
    out: Dict[str, Dict[str, float]] = {}
    for d in ops:
        for name, s, e in d:
            if name.startswith(trace.CONTAINERS):
                continue
            path = tf_op.get(name, "")
            parts = out.setdefault(scope_of(path, scopes) or "unscoped",
                                   dict.fromkeys(PARTS, 0.0))
            parts[part_of(path)] += (e - s) / n_dev / 1e9
    return out


def idle_by_label(window: Tuple[float, float],
                  device_ops: Sequence[trace.Event],
                  spans: Sequence[trace.Event]) -> Dict[str, float]:
    """Seconds of the window in which one device ran no op, each instant
    under the innermost host span open then (``other`` where none is): a
    gap is cut where a span opens or closes."""
    lo, hi = window
    spans = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
    out: Dict[str, float] = collections.defaultdict(float)
    for s, e in trace.idle_gaps(trace.clip(device_ops, lo, hi), lo, hi):
        cuts = sorted({s, e} | {t for _, a, b in spans for t in (a, b)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            out[trace.label_at((a + b) / 2, spans)] += (b - a) / 1e9
    return dict(out)


def program_scopes() -> Tuple[str, ...]:
    """The program's scope list; empty where the program has none."""
    from repro.kernels import ops
    return tuple(getattr(ops, "SCOPES", ()))


def reduce_file(path: str, span_names: Sequence[str] = SPANS,
                scopes: Optional[Sequence[str]] = None) -> Dict:
    """The one-chip reduction of ``trace.py`` with ``scopes`` (by default
    the program's) and ``idle_by_label`` added."""
    from jax.profiler import ProfileData
    window, ops, mods, spans = trace.events_from_profile(
        ProfileData.from_file(path), span_names)
    out = trace.reduce_events(window, ops[:1], mods[:1], spans)
    scopes = program_scopes() if scopes is None else scopes
    out["scopes"] = reduce_scopes(window, ops[:1], tf_ops(path), scopes)
    out["idle_by_label"] = idle_by_label(window, ops[0], spans)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="an .xplane.pb file")
    out = reduce_file(ap.parse_args(argv).path)
    for name, p in sorted(out["scopes"].items(),
                          key=lambda kv: -sum(kv[1].values())):
        print(f"scope: {name} total_s={sum(p.values())} "
              + " ".join(f"{k}_s={p[k]}" for k in PARTS), file=sys.stderr)
    for name, s in sorted(out["idle_by_label"].items(),
                          key=lambda kv: -kv[1]):
        print(f"idle: {name} s={s}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
