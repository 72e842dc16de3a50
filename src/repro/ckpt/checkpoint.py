"""Sharding-aware checkpointing (fault tolerance substrate).

Layout (one directory per step, committed atomically by rename):

    <root>/step_00000120/
        manifest.json       # tree structure + shapes/dtypes + metadata
        leaf_00000.npy ...  # one file per pytree leaf

* ``save_checkpoint``  — synchronous, atomic (tmp dir + rename), fsync'd
  manifest; safe against a node dying mid-write.
* ``AsyncCheckpointer`` — background-thread writer: the train loop only
  pays for the device->host copy, the file I/O overlaps with compute.
* ``load_checkpoint``  — rebuilds the tree; with ``shardings=`` it
  device_puts every leaf with the *target* sharding, which is how elastic
  restarts reshard a checkpoint onto a different mesh size.

Supports nested dict / list / tuple pytrees of array leaves.
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import tempfile
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


def _flatten(tree: Any, prefix: str = "") -> List[Tuple[str, Any]]:
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(_flatten(tree[k], f"{prefix}/{k}"))
        return out
    if isinstance(tree, (list, tuple)):
        out = []
        for i, v in enumerate(tree):
            out.extend(_flatten(v, f"{prefix}/[{i}]"))
        return out
    return [(prefix, tree)]


def _structure(tree: Any) -> Any:
    if isinstance(tree, dict):
        return {"__kind__": "dict",
                "items": {k: _structure(v) for k, v in tree.items()}}
    if isinstance(tree, (list, tuple)):
        return {"__kind__": "list" if isinstance(tree, list) else "tuple",
                "items": [_structure(v) for v in tree]}
    return {"__kind__": "leaf"}


def _rebuild(struct: Any, leaves: "queue.SimpleQueue") -> Any:
    kind = struct["__kind__"]
    if kind == "dict":
        return {k: _rebuild(v, leaves)
                for k, v in sorted(struct["items"].items())}
    if kind in ("list", "tuple"):
        seq = [_rebuild(v, leaves) for v in struct["items"]]
        return seq if kind == "list" else tuple(seq)
    return leaves.get_nowait()


def _step_dir(root: str, step: int) -> str:
    return os.path.join(root, f"step_{step:08d}")


# numpy can't round-trip ml_dtypes (bfloat16, fp8) through np.save/np.load;
# store the raw bits and the logical dtype name in the manifest instead.
def _encode(arr: np.ndarray):
    dt = arr.dtype
    if dt.kind in "fiub?c" and dt.name in np.sctypeDict:
        try:
            np.dtype(dt.name)
            if not dt.metadata and dt.name not in ("bfloat16",) and \
                    not dt.name.startswith("float8"):
                return arr, str(dt)
        except TypeError:
            pass
    # a device array's host copy need not be C-ordered (TPU layouts), and a
    # byte view needs a contiguous last axis
    raw = np.ascontiguousarray(arr).view(np.uint8)
    return raw.reshape(arr.shape + (dt.itemsize,)), str(dt)


def _decode(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    try:
        want = np.dtype(dtype_name)
        if arr.dtype == want:
            return arr
    except TypeError:
        want = None
    import ml_dtypes  # bundled with jax
    want = np.dtype(getattr(ml_dtypes, dtype_name, dtype_name))
    return arr.reshape(arr.shape[:-1] + (-1,)).view(want).reshape(
        arr.shape[:-1])


def save_checkpoint(root: str, tree: Any, step: int,
                    meta: Optional[Dict[str, Any]] = None) -> str:
    """Atomic synchronous save. Returns the committed directory."""
    os.makedirs(root, exist_ok=True)
    final = _step_dir(root, step)
    host = jax.tree.map(lambda x: np.asarray(x), tree)
    flat = _flatten(host)
    tmp = tempfile.mkdtemp(prefix=".ckpt_tmp_", dir=root)
    try:
        names = []
        for i, (path, arr) in enumerate(flat):
            fname = f"leaf_{i:05d}.npy"
            enc, dtype_name = _encode(arr)
            np.save(os.path.join(tmp, fname), enc)
            names.append({"path": path, "file": fname,
                          "shape": list(arr.shape), "dtype": dtype_name})
        manifest = {"step": step, "leaves": names,
                    "structure": _structure(host), "meta": meta or {}}
        mpath = os.path.join(tmp, "manifest.json")
        with open(mpath, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):      # overwrite = replace atomically-ish
            shutil.rmtree(final)
        os.rename(tmp, final)
        return final
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def latest_step(root: str) -> Optional[int]:
    if not os.path.isdir(root):
        return None
    steps = []
    for d in os.listdir(root):
        if d.startswith("step_") and os.path.exists(
                os.path.join(root, d, "manifest.json")):
            steps.append(int(d.split("_")[1]))
    return max(steps) if steps else None


def load_checkpoint(root: str, step: Optional[int] = None, *,
                    shardings: Any = None) -> Tuple[Any, Dict[str, Any]]:
    """Returns (tree, manifest_meta). ``shardings``: matching pytree of
    NamedShardings (or None) — leaves are device_put with them (elastic
    restart onto a new mesh reshards here)."""
    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {root}")
    d = _step_dir(root, step)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    q: "queue.SimpleQueue" = queue.SimpleQueue()
    for leaf in manifest["leaves"]:
        raw = np.load(os.path.join(d, leaf["file"]))
        q.put(_decode(raw, leaf["dtype"]))
    tree = _rebuild(manifest["structure"], q)
    if shardings is not None:
        tree = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            tree, shardings,
            is_leaf=lambda x: isinstance(x, np.ndarray))
    manifest["meta"]["step"] = manifest["step"]
    return tree, manifest["meta"]


class AsyncCheckpointer:
    """Single background writer; the caller pays only the host copy."""

    def __init__(self, root: str, *, keep: int = 3):
        self.root = root
        self.keep = keep
        self._q: "queue.Queue" = queue.Queue()
        self._err: Optional[BaseException] = None
        self._t = threading.Thread(target=self._loop, daemon=True,
                                   name="ckpt-writer")
        self._t.start()

    def _gc(self) -> None:
        steps = sorted(
            int(d.split("_")[1]) for d in os.listdir(self.root)
            if d.startswith("step_"))
        for s in steps[:-self.keep]:
            shutil.rmtree(_step_dir(self.root, s), ignore_errors=True)

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            tree, step, meta = item
            try:
                save_checkpoint(self.root, tree, step, meta)
                if self.keep:
                    self._gc()
            except BaseException as e:  # surfaced on next save()/close()
                self._err = e

    def save(self, tree: Any, step: int,
             meta: Optional[Dict[str, Any]] = None) -> None:
        if self._err is not None:
            raise RuntimeError("async checkpoint failed") from self._err
        host = jax.tree.map(lambda x: np.asarray(x), tree)  # sync copy
        self._q.put((host, step, meta))

    def close(self) -> None:
        self._q.put(None)
        self._t.join()
        if self._err is not None:
            raise RuntimeError("async checkpoint failed") from self._err
