"""zamba2-7b at its smoke size (two shared blocks, two hybrid layers, two
B/C groups) against the plain float32 reference of
``tests/zamba2_reference.py``, on the CPU in float32 with seeded weights:
logits, loss and every leaf's gradient of the training path; prefill then
decode through the caches; and the grouped SSD and norm against a loop
over the groups.

Tolerances: the program and the reference compute the same float32
mathematics in other orders (the SSD chunked against quadratic, attention
in key blocks with an online softmax against one softmax, the cross
entropy in vocabulary blocks), at "highest" matmul precision, so they
agree to float32 rounding grown through the layers: the logits to 7e-6 of
the largest, held to 1e-4.  The same weights in bfloat16 miss the logits
by 0.29 of the largest."""
import jax
import jax.numpy as jnp
import pytest

import zamba2_reference as ref
from repro.configs.base import RunConfig, get_smoke_config
from repro.kernels import ops
from repro.kernels.ref import ssd_ref
from repro.models import layers as L
from repro.models import params as P
from repro.models import registry
from repro.serve import engine
from repro.train.loss import lm_loss

ARCH = "zamba2-7b"
# float32 throughout: the cross entropy's logits too
RUN = RunConfig(remat="full", ce_block_v=64, ce_dtype="float32")
TOL = 1e-4


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config(ARCH)
    assert cfg.ssm_groups == 2 and cfg.num_mem_blocks == 2
    assert len(cfg.hybrid_layer_ids) == 2
    params = P.cast_tree(P.materialize(jax.random.PRNGKey(3),
                                       registry.param_defs(cfg)),
                         jnp.float32)
    # norm weights and biases off their init of ones and zeros, so that
    # a mix-up of two of them shows
    params = jax.tree.map(
        lambda a, k: a + 0.1 * jax.random.normal(k, a.shape),
        params, _keys(params, 4))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 40), 2,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
             "loss_mask": (jax.random.uniform(jax.random.PRNGKey(6),
                                              (2, 39)) > 0.2
                           ).astype(jnp.float32)}
    return cfg, params, batch


def _keys(tree, seed):
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(
        treedef, list(jax.random.split(jax.random.PRNGKey(seed),
                                       len(leaves))))


def _close(got, want, tol=TOL):
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= tol * scale, (err, scale)


def test_logits_match_reference(setup):
    cfg, params, batch = setup
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, b: L.logits_out(
            p["embed"], cfg, RUN, registry.forward(p, cfg, RUN, b)))(
                params, batch)
        want = _ref_logits(cfg)(params, batch["tokens"])
    _close(got, want)


def _ref_logits(cfg):
    return jax.jit(jax.vmap(lambda p, t: ref.logits(p, t, cfg),
                            in_axes=(None, 0)))


def test_loss_and_grads_match_reference(setup):
    cfg, params, batch = setup
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(p, cfg, RUN, batch), has_aux=True))(params)
    want_loss, want = jax.jit(
        lambda p, b: ref.loss_and_grads(p, b, cfg))(params, batch)
    assert abs(float(loss) - float(want_loss)) <= TOL * float(want_loss)
    flat = dict(zip(map(jax.tree_util.keystr,
                        jax.tree_util.tree_flatten_with_path(grads)[0]),
                    jax.tree.leaves(grads)))
    flat_want = jax.tree.leaves(want)
    assert len(flat) == len(flat_want)
    for (name, g), w in zip(flat.items(), flat_want):
        err = float(jnp.linalg.norm(g - w))
        assert err <= TOL * float(jnp.linalg.norm(w)) + 1e-9, (name, err)


def test_prefill_then_decode_match_reference(setup):
    """Prefill 24 tokens, then decode the rest one at a time through the
    Mamba2 states and the hybrid layers' KV caches: every step's logits
    are the reference's full forward pass at that position."""
    cfg, params, batch = setup
    toks = batch["tokens"]
    T, k = toks.shape[1], 24
    run = RUN.replace(remat="none")
    decode = jax.jit(lambda p, t, c, pos: registry.decode(p, cfg, run, t, c,
                                                          pos))
    with jax.default_matmul_precision("highest"):
        want = _ref_logits(cfg)(params, toks)
        cache = engine.init_cache(cfg, toks.shape[0], T)
        cache = jax.tree.map(lambda a: a.astype(jnp.float32)
                             if a.dtype == jnp.bfloat16 else a, cache)
        lg, cache = registry.prefill(params, cfg, run, {"tokens": toks[:, :k]},
                                     cache)
        got = [lg[:, -1]]
        for t in range(k, T):
            lg, cache = decode(params, toks[:, t:t + 1], cache,
                               jnp.asarray(t, jnp.int32))
            got.append(lg[:, -1])
    _close(jnp.stack(got, axis=1), want[:, k - 1:])


def test_grouped_ssd_matches_a_loop_over_groups():
    """G = 2: each group's heads read their own B and C."""
    Bsz, S, H, P_, G, N = 2, 48, 6, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (Bsz, S, H, P_))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bsz, S, G, N))
    Cm = jax.random.normal(ks[4], (Bsz, S, G, N))
    K = H // G
    with jax.default_matmul_precision("highest"):
        got = ssd_ref(x, dt, A, Bm, Cm, chunk=16)
        want = jnp.concatenate(
            [ssd_ref(x[:, :, g * K:(g + 1) * K], dt[:, :, g * K:(g + 1) * K],
                     A[g * K:(g + 1) * K], Bm[:, :, g:g + 1],
                     Cm[:, :, g:g + 1], chunk=16) for g in range(G)], axis=2)
    _close(got, want, 1e-5)


def test_grouped_norm_matches_a_loop_over_groups():
    """The grouped RMSNorm and its gradients: each group of d_inner / G
    channels normalised on its own, with its own slice of the weight."""
    G, D = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, G * D))
    w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (G * D,))
    ct = jax.random.normal(jax.random.PRNGKey(10), x.shape)

    def grouped(x, w):
        y = ops.rmsnorm(x.reshape(3, 5, G, D), w.reshape(G, D), eps=1e-5)
        return jnp.sum(y.reshape(x.shape) * ct)

    def looped(x, w):
        y = jnp.concatenate(
            [ops.rmsnorm(x[..., g * D:(g + 1) * D], w[g * D:(g + 1) * D],
                         eps=1e-5) for g in range(G)], -1)
        return jnp.sum(y * ct)

    for a, b in zip(jax.grad(grouped, (0, 1))(x, w),
                    jax.grad(looped, (0, 1))(x, w)):
        _close(a, b, 1e-5)
