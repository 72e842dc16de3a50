"""The plain references against the program at the smoke sizes on the
CPU: in float32 they agree to rounding; the weights, the corpus and its
packing are the program's to the bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import corpus as C
from chipbench import weights as W
from chipbench.reference import mamba2
from chipbench.reference.numerics import F32

CASES = [("mamba2-130m", mamba2)]
SEED = 2 ** 33 + 5


def smoke(arch):
    from repro.configs.base import get_smoke_config
    cfg = get_smoke_config(arch)
    return cfg, dict(dataclasses.asdict(cfg), param_dtype="bfloat16")


@pytest.mark.parametrize("arch,ref", CASES)
def test_forward_matches_program_in_f32(arch, ref):
    from repro.configs.base import RunConfig
    from repro.models import layers as L
    from repro.models import registry
    cfg, m = smoke(arch)
    spec = ref.param_spec(m)
    W.check_layout(spec, W.flatten(registry.param_defs(cfg)))
    flat = W.make_params(spec, SEED)
    p32 = {k: v.astype(jnp.float32) for k, v in flat.items()}
    tree = W.nest(p32)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 2,
                              cfg.vocab_size)
    run = RunConfig(remat="none")
    with jax.default_matmul_precision("highest"):
        h = registry.forward(tree, cfg, run, {"tokens": toks})
        prog = L.logits_out(tree["embed"], cfg, run, h)
    want = jnp.stack([ref.logits(p32, toks[r], m) for r in range(2)])
    assert float(jnp.max(jnp.abs(prog - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("block", [16, 40, 96])
def test_ssd_blocks_leave_the_output_unchanged(block):
    """Output rows taken in blocks give what the whole square gives."""
    T, H, P, N = 96, 3, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (T, N))
    C = jax.random.normal(ks[4], (T, N))
    with jax.default_matmul_precision("highest"):
        whole = mamba2.ssd_quadratic(x, dt, A, B, C, F32, block=T)
        got = mamba2.ssd_quadratic(x, dt, A, B, C, F32, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_weights_are_the_same_in_any_program():
    _, m = smoke("mamba2-130m")
    spec = mamba2.param_spec(m)
    a = W.make_params(spec, SEED)
    inside = jax.jit(lambda k: (W.leaf_maker(spec)(k), k + 1))(
        W.seed_key(SEED, 1))[0]
    for k in a:
        assert a[k].dtype == inside[k].dtype
        np.testing.assert_array_equal(np.asarray(a[k], np.float32),
                                      np.asarray(inside[k], np.float32))
    b = W.make_params(spec, SEED + 1)
    assert not np.array_equal(np.asarray(a["embed/tok"], np.float32),
                              np.asarray(b["embed/tok"], np.float32))


def test_packing_matches_program_transform():
    from repro.carousel.transform import pack_documents
    traffic = {"docs_per_shard": 16, "mean_doc_len": 300,
               "min_doc_len": 8, "doc_lengths_seed": 1, "seq_len": 512}
    c = C.Corpus(SEED, traffic, 1000)
    for shard in range(4):
        want = pack_documents(c.docs(shard), 512)
        got = c.rows(shard)
        assert C.row_digests(got) == C.row_digests(want)
