"""Compile-only checks for TPU v5e: the four Pallas kernels (Mosaic,
interpret=False), the mamba2-130m train step and the XLA SSD path at the
training cell's widths, and the SSD kernels on a four-chip mesh, compiled
for a described ``v5e:2x2`` topology.  Nothing runs; this catches what
the chip's compiler refuses (block tiling, VMEM, HBM) or lowers off the
matrix unit, without a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library."""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.base import RunConfig, ShapeConfig, get_config
from repro.kernels.cross_entropy import cross_entropy_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels import ops
from repro.kernels.ops import SCOPES
from repro.kernels.ref import ssd_ref
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.sharding import ShardingRules, batch_shardings, use_rules
from repro.train.step import abstract_state, make_train_step, state_shardings

V5E_HBM_BYTES = 16 * 2**30 * 0.984   # 15.75 GiB usable per v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (name, kernel at interpret=False, argument shapes and dtypes); widths of
# mamba2-130m (d_model 768, 24 SSD heads of 64, state 128, vocab 50280,
# 16384 tokens per step) and a 20-head, 128-dim, 4096-token attention.
KERNELS = [
    ("rmsnorm", lambda x, w: rmsnorm_pallas(x, w, interpret=False),
     [((16384, 768), BF), ((768,), BF)]),
    ("flash_attention",
     lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
     [((1, 4096, 20, 128), BF)] * 3),
    ("ssd", lambda *a: ssd_pallas(*a, chunk=128, interpret=False),
     [((8, 2048, 24, 64), BF), ((8, 2048, 24), F32), ((24,), F32),
      ((8, 2048, 1, 128), BF), ((8, 2048, 1, 128), BF)]),
    ("cross_entropy", lambda *a: cross_entropy_pallas(*a, interpret=False),
     [((16384, 768), BF), ((50280, 768), BF), ((16384,), I32)]),
]


@pytest.mark.parametrize("name,fn,shapes", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.fixture(scope="module")
def mamba2_step(topo):
    """The step run_training builds for the training cell: mamba2-130m at
    published widths and depth (24 layers, its chunk_size 256, the
    vocabulary padded to 50288), 32 rows of 2048 tokens, compiled on a
    one-chip mesh of the described topology."""
    cfg = get_config("mamba2-130m").replace(ssm_chunk=256, vocab_size=50288)
    run = RunConfig(total_steps=10, warmup_steps=2, ce_block_v=6286)
    rules = ShardingRules(make_mesh((1, 1), ("data", "model"),
                                    devices=topo.devices[:1]))
    st_sh = state_shardings(cfg, rules)
    with_sharding = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sh)
    state = jax.tree.map(with_sharding, abstract_state(cfg, run), st_sh)
    batch = registry.train_input_specs(cfg, ShapeConfig("t", 2048, 32,
                                                        "train"))
    batch = jax.tree.map(with_sharding, batch, batch_shardings(rules, batch))
    with use_rules(rules):
        return jax.jit(make_train_step(cfg, run),
                       out_shardings=(st_sh, None),
                       donate_argnums=(0,)).lower(state, batch).compile()


def test_mamba2_130m_train_step_compiles_for_one_v5e(mamba2_step):
    """The step fits one chip's HBM."""
    mem = mamba2_step.memory_analysis()
    assert 0 < mem.peak_memory_in_bytes < V5E_HBM_BYTES


def test_mamba2_130m_train_step_matmuls_are_scoped(mamba2_step):
    """Every matrix product of the chip's program (a convolution on the
    TPU) names a scope of ``SCOPES`` in its ``op_name``."""
    convs = [ln for ln in mamba2_step.as_text().splitlines()
             if re.search(r" convolution\(", ln)]
    assert convs
    for ln in convs:
        m = re.search(r'op_name="([^"]*)"', ln)
        assert m and set(re.split(r"[/()]", m.group(1))) & set(SCOPES), ln


def test_mamba2_130m_train_step_runs_the_ssd_kernels(mamba2_step):
    """On the TPU the chunk scan's chunk-local parts (each chunk's state,
    and its output within and from before the chunk) run in the Pallas
    kernels, forward (twice: the pass and its recomputation) and
    backward, under the ``ssd`` scope."""
    hlo = mamba2_step.as_text()
    for name in ("ssd_intra_fwd", "ssd_intra_bwd", "ssd_states_fwd",
                 "ssd_states_bwd"):
        calls = re.findall(rf"%{name}[.\d]* = .* custom-call\(.*", hlo)
        assert calls, name
        for ln in calls:
            assert "/ssd/" in re.search(r'op_name="([^"]*)"', ln)[1], ln


def test_mamba2_130m_train_step_forms_no_per_head_score_tiles(mamba2_step):
    """No float32 value of the program is as large as one (Q, Q) tile per
    head and chunk of the batch, as ``ssd_ref``'s decay and score matrices
    are: only the cross entropy's (tokens, vocabulary block) logits."""
    B, C, H, Q = 32, 2048 // 256, 24, 256
    big = set()
    for m in re.finditer(r"= \(?f32\[([\d,]+)\]", mamba2_step.as_text()):
        dims = [int(d) for d in m[1].split(",")]
        if math.prod(dims) >= B * C * H * Q * Q:
            big.add(tuple(dims))
    assert big <= {(B * 2048, 6286)}, big


def test_mamba2_130m_train_step_peak_memory(mamba2_step):
    """The kernels' step needs no more device memory than the XLA path's
    (8,231,715,840 bytes compiled with ``ssd_ref`` in the scan's place)."""
    assert mamba2_step.memory_analysis().peak_memory_in_bytes <= 8_231_715_840


def test_ssd_kernels_on_a_four_chip_mesh_gather_nothing(topo):
    """Under the rules of a (4, 1) mesh with the batch on "data", the
    kernels run on each chip's own rows: the SSD's forward and backward
    pass hold no all-gather, all-to-all or permute of an operand."""
    rules = ShardingRules(make_mesh((4, 1), ("data", "model"),
                                    devices=topo.devices))
    B, S, H, P, G, N = 8, 512, 8, 64, 2, 16
    rows = NamedSharding(rules.mesh, PartitionSpec("data"))
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in
            [((B, S, H, P), BF, rows), ((B, S, H), F32, rows),
             ((H,), F32, NamedSharding(rules.mesh, PartitionSpec())),
             ((B, S, G, N), BF, rows), ((B, S, G, N), BF, rows)]]

    def loss(*a):
        return jnp.sum(ops.ssd(*a, chunk=128).astype(F32))

    with use_rules(rules):
        hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
                      ).lower(*args).compile().as_text()
    for name in ("ssd_intra_fwd", "ssd_intra_bwd", "ssd_states_fwd",
                 "ssd_states_bwd"):
        assert name in hlo, name
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert f" {op}(" not in hlo and f" {op}-start(" not in hlo, op


# B, S, H, P, G, N, chunk of the training cell: mamba2-130m, 32 rows of
# 2048 tokens, chunk 256
SSD_CELL = (32, 2048, 24, 64, 1, 128, 256)


@pytest.fixture(scope="module")
def ssd_ref_grad_hlo(one_chip):
    """The chip's program for the XLA SSD path (``ssd_ref``), forward and
    backward, at the widths the training cell runs."""
    B, S, H, P, G, N, chunk = SSD_CELL
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            [((B, S, H, P), BF), ((B, S, H), F32), ((H,), F32),
             ((B, S, G, N), BF), ((B, S, G, N), BF)]]

    def loss(x, dt, A, Bm, Cm):
        return jnp.sum(ssd_ref(x, dt, A, Bm, Cm, chunk=chunk).astype(F32))

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    return jax.jit(grad).lower(*args).compile().as_text()


def test_ssd_ref_contractions_are_not_loop_fusions(ssd_ref_grad_hlo):
    """Every contraction of the chunk scan, forward and backward, is a
    matrix-unit dot: no elementwise loop fusion is an einsum's
    ``dot_general``."""
    loops = [ln for ln in ssd_ref_grad_hlo.splitlines()
             if "kind=kLoop" in ln
             and re.search(r'op_name="[^"]*dot_general', ln)]
    assert not loops, loops[0][:300]


def test_ssd_ref_backward_sums_no_head_repeated_b_or_c(ssd_ref_grad_hlo):
    """B and C are not repeated over the heads of their group: no reduction
    sums a head axis away into a gradient shaped like B or C."""
    B, S, H, P, G, N, chunk = SSD_CELL
    hlo = ssd_ref_grad_hlo

    def dims(shape):
        """Sizes of an HLO shape without its unit axes, which XLA may
        drop (G = 1)."""
        return [int(d) for d in shape.split(",") if d not in ("", "1")]

    shapes = {m[1]: m[2] for m in
              re.finditer(r"(%[\w.-]+) = f32\[([\d,]*)\]", hlo)}
    sums = re.finditer(r"= f32\[([\d,]*)\]\S* reduce\((%[\w.-]+), "
                       r"[^)]*\), dimensions=\{([\d,]*)\}", hlo)
    bc_shape = dims(f"{B},{S // chunk},{chunk},{G},{N}")
    for m in sums:
        operand = [int(d) for d in shapes[m[2]].split(",") if d]
        summed = {operand[int(d)] for d in m[3].split(",")}
        assert not (dims(m[1]) == bc_shape and H in summed), m[0]
