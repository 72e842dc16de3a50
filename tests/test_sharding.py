"""Sharding rules: logical resolution, divisibility fallbacks, dedup."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from repro.models.params import pdef
from repro.sharding import ShardingRules, param_specs


@pytest.fixture(scope="module")
def mesh():
    # 1 real device; mesh shape (1, 1) keeps axis NAMES resolvable
    return jax.make_mesh((1, 1), ("data", "model"))


def rules(mesh, model=16, data=16):
    """Fake axis sizes for resolution tests via a stub mesh-shape view."""
    r = ShardingRules(mesh)
    r.mesh = type("M", (), {"shape": {"data": data, "model": model}})()
    return r


def test_divisible_dims_shard(mesh):
    r = rules(mesh)
    assert r.spec(("embed", "ffn"), (4096, 11008)) == \
        PartitionSpec("data", "model")


def test_non_divisible_falls_back_to_replicated(mesh):
    r = rules(mesh)
    # 40 heads % 16 != 0 -> replicated
    assert r.spec(("heads",), (40,)) == PartitionSpec(None)
    # 6 heads (whisper)
    assert r.spec(("heads",), (6,)) == PartitionSpec(None)


def test_batch_uses_pod_and_data_axes(mesh):
    r = ShardingRules(mesh)
    r.mesh = type("M", (), {"shape": {"pod": 2, "data": 16, "model": 16}})()
    assert r.spec(("batch", None), (256, 128)) == \
        PartitionSpec(("pod", "data"), None)


def test_batch_prefix_fallback(mesh):
    """batch=1 (long_500k): falls back through prefixes to replicated."""
    r = ShardingRules(mesh)
    r.mesh = type("M", (), {"shape": {"pod": 2, "data": 16, "model": 16}})()
    assert r.spec(("batch",), (1,)) == PartitionSpec(None)
    # batch=2: divisible by pod prefix only
    assert r.spec(("batch",), (2,)) == PartitionSpec("pod")


def test_duplicate_axis_dedup(mesh):
    """MoE weights tag both 'expert' and 'ffn' -> model axis used once."""
    r = rules(mesh)
    # qwen3: 128 experts divide -> expert wins, ffn dropped
    assert r.spec(("layers", "expert", "embed", "ffn"),
                  (94, 128, 4096, 1536)) == \
        PartitionSpec(None, "model", "data", None)
    # mixtral: 8 experts don't divide -> ffn gets the model axis
    assert r.spec(("layers", "expert", "embed", "ffn"),
                  (32, 8, 4096, 14336)) == \
        PartitionSpec(None, None, "data", "model")


def test_kv_cache_dedup_kvseq_over_heads(mesh):
    r = rules(mesh)
    spec = r.spec(("layers", "batch", "kv_seq", "heads", None),
                  (38, 128, 32768, 32, 64))
    # kv_seq claims the model axis first; heads dropped
    assert spec == PartitionSpec(None, "data", "model", None, None)


def test_param_specs_tree(mesh):
    r = rules(mesh)
    defs = {"w": pdef((4096, 1024), ("embed", "qkv")),
            "b": pdef((1024,), ("qkv",))}
    specs = param_specs(defs, r)
    assert specs["w"] == PartitionSpec("data", "model")
    assert specs["b"] == PartitionSpec("model")


def test_vocab_fallback_on_odd_vocab(mesh):
    r = rules(mesh)
    # mamba2 vocab 50280 % 16 != 0 -> replicated
    assert r.spec(("vocab", "embed"), (50280, 768)) == \
        PartitionSpec(None, "data")
    assert r.spec(("vocab", "embed"), (152064, 5120)) == \
        PartitionSpec("model", "data")


def test_constrain_noop_without_context():
    from repro.sharding import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


def test_launch_meshes_are_auto_and_constrain_under_jit():
    """Every mesh builder gives Auto axes, so ``constrain`` (which calls
    ``with_sharding_constraint``) works inside jit with rules in effect."""
    from jax.sharding import AxisType
    from repro.launch import mesh as mesh_lib
    from repro.sharding import constrain, use_rules

    # production shapes need 256/512 devices: repeat the one CPU device,
    # enough to build the Mesh object and read its axis types
    many = jax.devices() * 512
    meshes = [mesh_lib.make_production_mesh(devices=many),
              mesh_lib.make_production_mesh(multi_pod=True, devices=many),
              mesh_lib.make_mesh((1, 1), ("data", "model")),
              mesh_lib.make_host_mesh()]
    for m in meshes:
        assert set(m.axis_types) == {AxisType.Auto}, m
    for m in meshes[2:]:
        with use_rules(ShardingRules(m)):
            y = jax.jit(lambda x: constrain(x * 2, "batch", "embed"))(
                jnp.ones((4, 8)))
        assert float(y.sum()) == 64.0
