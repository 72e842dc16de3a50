"""Checkpoint/restart: atomic commit, async writer, resume bit-equality,
elastic resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (AsyncCheckpointer, latest_step, load_checkpoint,
                        save_checkpoint)
from repro.configs.base import RunConfig, ShapeConfig, get_smoke_config
from repro.models import registry
from repro.train.step import init_state, make_train_step


def _tree():
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.bfloat16)},
        "opt": {"m": [jnp.zeros((2,)), jnp.full((3,), 7.0)],
                "step": jnp.asarray(5, jnp.int32)},
        "mixed": (jnp.asarray([1, 2], jnp.int8),),
    }


def _assert_tree_equal(a, b):
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_round_trip(tmp_path):
    t = _tree()
    d = save_checkpoint(str(tmp_path), t, 120, meta={"loss": 1.5})
    assert os.path.basename(d) == "step_00000120"
    t2, meta = load_checkpoint(str(tmp_path))
    _assert_tree_equal(t, t2)
    assert meta["loss"] == 1.5 and meta["step"] == 120


def test_round_trip_non_contiguous_bf16(tmp_path):
    """Host copies of device arrays may come back in another memory order
    (TPU layouts); the raw-bits encoding of bfloat16 must still work, as
    must a 0-d bfloat16 leaf."""
    base = np.arange(24, dtype=np.float32).reshape(4, 6)
    t = {"w": base.astype(jnp.bfloat16).T, "s": np.asarray(3.5, jnp.bfloat16)}
    assert not t["w"].flags.c_contiguous
    save_checkpoint(str(tmp_path), t, 1)
    t2, _ = load_checkpoint(str(tmp_path))
    _assert_tree_equal(t, t2)


def test_latest_step_and_overwrite(tmp_path):
    save_checkpoint(str(tmp_path), _tree(), 1)
    save_checkpoint(str(tmp_path), _tree(), 3)
    save_checkpoint(str(tmp_path), _tree(), 2)
    assert latest_step(str(tmp_path)) == 3
    save_checkpoint(str(tmp_path), _tree(), 3)  # idempotent overwrite
    assert latest_step(str(tmp_path)) == 3


def test_no_partial_commit(tmp_path):
    """A crashed save (simulated) leaves no committed step dir."""
    class Boom(Exception):
        pass

    bad = {"x": jnp.ones((2,))}
    orig = np.save
    calls = {"n": 0}

    def exploding_save(f, arr, **kw):
        calls["n"] += 1
        raise Boom()

    np.save = exploding_save
    try:
        with pytest.raises(Boom):
            save_checkpoint(str(tmp_path), bad, 9)
    finally:
        np.save = orig
    assert latest_step(str(tmp_path)) is None
    assert not [d for d in os.listdir(tmp_path) if d.startswith("step_")]


def test_async_checkpointer_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in range(5):
        ck.save(_tree(), s)
    ck.close()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3, 4]


def test_resume_bit_equality(tmp_path):
    """Training resumed from a checkpoint matches uninterrupted training."""
    cfg = get_smoke_config("yi-6b")
    run = RunConfig(ce_block_v=64)
    shape = ShapeConfig("s", 16, 4, "train")
    step = jax.jit(make_train_step(cfg, run))

    def batch(i):
        return registry.synth_inputs(jax.random.PRNGKey(100 + i), cfg,
                                     shape, "train")

    s = init_state(jax.random.PRNGKey(0), cfg, run)
    for i in range(2):
        s, _ = step(s, batch(i))
    save_checkpoint(str(tmp_path), s, 2)
    for i in range(2, 4):
        s, _ = step(s, batch(i))
    ref_loss = None
    s_resumed, _ = load_checkpoint(str(tmp_path), 2)
    s_resumed = jax.tree.map(jnp.asarray, s_resumed)
    for i in range(2, 4):
        s_resumed, m = step(s_resumed, batch(i))
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(s_resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_reshard_roundtrip(tmp_path):
    """Loading with target shardings device_puts onto the current mesh —
    the elastic-restart path (1 device here, arbitrary shapes)."""
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = jax.make_mesh((1,), ("data",))
    t = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_checkpoint(str(tmp_path), t, 0)
    sh = {"w": NamedSharding(mesh, PartitionSpec("data", None))}
    t2, _ = load_checkpoint(str(tmp_path), 0, shardings=sh)
    assert t2["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(t2["w"]), np.asarray(t["w"]))
