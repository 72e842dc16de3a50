"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis
property tests (on the CPU backend the kernels run in interpret mode,
which executes the kernel body in Python)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    # No hypothesis on this machine: the property tests skip but the
    # parametrized sweeps below must still collect and run.  The stubs
    # keep the module-level @given/@settings/st.* expressions valid.
    def given(*args, **kwargs):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*args, **kwargs):
        return lambda f: f

    class _NullStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NullStrategies()

from repro.kernels.cross_entropy import cross_entropy_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ref import (attention_naive, cross_entropy_direct_ref,
                               cross_entropy_blockwise_ref,
                               flash_attention_ref, rmsnorm_ref,
                               ssd_decode_ref, ssd_ref, ssd_sequential_ref)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_pallas

jax.config.update("jax_default_matmul_precision", "highest")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(8, 128), (3, 7, 384), (1, 513)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype)
    a = rmsnorm_pallas(x, w, block_rows=4)
    b = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_tol(dtype))


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 64), d=st.integers(8, 256),
       seed=st.integers(0, 2**30))
def test_rmsnorm_property(rows, d, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, d), jnp.float32)
    w = jnp.ones((d,), jnp.float32)
    y = rmsnorm_pallas(x, w, block_rows=16)
    # invariant: output row RMS == 1 (up to eps)
    rms = np.sqrt(np.mean(np.asarray(y, np.float32) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3, atol=1e-3)


# ------------------------------------------------------- flash attention

CASES = [
    # B, Sq, Sk, Hq, Hkv, D, causal, window, q_off, kv_len
    (2, 128, 128, 4, 2, 64, True, 0, 0, None),
    (1, 100, 160, 6, 6, 64, True, 0, 0, None),      # whisper-ish heads
    (2, 1, 256, 8, 2, 128, True, 0, 200, 201),      # decode
    (2, 64, 256, 4, 4, 64, True, 48, 0, None),      # sliding window
    (1, 96, 160, 4, 2, 64, False, 0, 0, None),      # cross attention
    (1, 80, 80, 40, 40, 32, True, 0, 0, None),      # qwen32b head count
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_sweep(case, dtype):
    B, Sq, Sk, Hq, Hkv, D, causal, sw, qoff, kvl = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    a = flash_attention_pallas(q, k, v, causal=causal, sliding_window=sw,
                               q_offset=qoff, kv_len=kvl,
                               block_q=32, block_k=64)
    b = attention_naive(q, k, v, causal=causal, sliding_window=sw,
                        q_offset=qoff, kv_len=kvl)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", CASES)
def test_flash_ref_matches_naive(case):
    B, Sq, Sk, Hq, Hkv, D, causal, sw, qoff, kvl = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), jnp.float32)
    a = flash_attention_ref(q, k, v, causal=causal, sliding_window=sw,
                            q_offset=qoff, kv_len=kvl, block_k=48)
    b = attention_naive(q, k, v, causal=causal, sliding_window=sw,
                        q_offset=qoff, kv_len=kvl)
    np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5)


def test_flash_ref_custom_vjp_matches_autodiff_oracle():
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, 40, 8, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 2, 32), jnp.float32)
    do = jax.random.normal(ks[3], (2, 40, 8, 32), jnp.float32)
    f = lambda *a: jnp.vdot(flash_attention_ref(*a, block_k=16), do)
    g = lambda *a: jnp.vdot(attention_naive(*a), do)
    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(sq=st.integers(1, 80), sk=st.integers(8, 96),
       hq=st.sampled_from([2, 4, 6]), g=st.sampled_from([1, 2]),
       seed=st.integers(0, 2**30))
def test_flash_pallas_property(sq, sk, hq, g, seed):
    """Property: pallas flash == naive attention on random shapes."""
    if hq % g:
        g = 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, sq, hq, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, hq // g, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, hq // g, 32), jnp.float32)
    a = flash_attention_pallas(q, k, v, causal=False, block_q=16,
                               block_k=32)
    b = attention_naive(q, k, v, causal=False)
    np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)


# ----------------------------------------------------------------- SSD

SSD_CASES = [
    # B, S, H, P, G, N, chunk
    (2, 96, 4, 16, 1, 32, 32),
    (1, 130, 6, 32, 2, 16, 64),   # ragged tail
    (2, 64, 2, 64, 1, 128, 32),   # mamba2-130m-like dims
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_sweep(case, dtype):
    B, S, H, P, G, N, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = (jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
         * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
          * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
          * 0.3).astype(dtype)
    y1 = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk)
    y2, _ = ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 3e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 3e-4)


def test_ssd_ref_chunk_invariance():
    """Property: chunk size must not change the result (SSD identity)."""
    B, S, H, P, G, N = 2, 120, 4, 16, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32) * 0.3
    outs = [ssd_ref(x, dt, A, Bm, Cm, chunk=c) for c in (16, 40, 120)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-4, atol=2e-4)


def test_ssd_state_chaining_equals_decode():
    """Prefill state + single-token decode == one longer prefill."""
    B, S, H, P, G, N = 1, 33, 2, 8, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32) * 0.3
    y_full, _ = ssd_ref(x, dt, A, Bm, Cm, chunk=16, return_state=True)
    _, h = ssd_ref(x[:, :-1], dt[:, :-1], A, Bm[:, :-1], Cm[:, :-1],
                   chunk=16, return_state=True)
    y_dec, _ = ssd_decode_ref(x[:, -1], dt[:, -1], A, Bm[:, -1], Cm[:, -1],
                              h)
    np.testing.assert_allclose(y_full[:, -1], y_dec, rtol=2e-4, atol=2e-4)


SSD_GRAD_CASES = [
    # B, S, H, P, G, N, chunk, init_state
    (2, 16, 4, 8, 1, 16, 8, False),
    (2, 16, 4, 8, 2, 16, 4, False),
    (1, 13, 6, 8, 1, 16, 4, True),     # ragged tail
    (1, 13, 4, 8, 2, 16, 8, True),     # ragged tail
    (2, 16, 4, 8, 2, 8, 8, True),
]


@pytest.mark.parametrize("case", SSD_GRAD_CASES)
def test_ssd_ref_grad_matches_sequential(case):
    """The chunked scan's backward pass: gradients of a loss on the output
    and on the returned state, for every input, against autodiff through
    the token-by-token recurrence."""
    B, S, H, P, G, N, chunk, with_init = case
    rng = np.random.default_rng(7)
    normal = lambda *shape: rng.standard_normal(shape, np.float32)
    x = normal(B, S, H, P) * 0.5
    dt = np.log1p(np.exp(normal(B, S, H)))
    A = -np.exp(normal(H) * 0.3)
    Bm, Cm = normal(B, S, G, N) * 0.3, normal(B, S, G, N) * 0.3
    h0 = normal(B, H, P, N) * 0.3
    wy, wh = normal(B, S, H, P), normal(B, H, P, N)

    def loss(fn, x, dt, A, Bm, Cm, h0):
        y, h = fn(x, dt, A, Bm, Cm, init_state=h0 if with_init else None)
        return jnp.sum(y * wy) + jnp.sum(h * wh)

    chunked = lambda *a, **k: ssd_ref(*a, chunk=chunk, return_state=True,
                                      **k)
    argnums = (1, 2, 3, 4, 5) + ((6,) if with_init else ())
    grad = lambda fn: jax.jit(jax.grad(loss, argnums), static_argnums=0)(
        fn, x, dt, A, Bm, Cm, h0)
    g1, g2 = grad(chunked), grad(ssd_sequential_ref)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "init_state"), g1, g2):
        scale = float(np.max(np.abs(b)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=name)


@settings(max_examples=15, deadline=None)
@given(s=st.integers(2, 70), chunk=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 2**30))
def test_ssd_pallas_property(s, chunk, seed):
    B, H, P, G, N = 1, 2, 8, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, s, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    Bm = jax.random.normal(ks[3], (B, s, G, N), jnp.float32) * 0.3
    Cm = jax.random.normal(ks[4], (B, s, G, N), jnp.float32) * 0.3
    y1, h1 = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, return_state=True)
    y2, h2 = ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y1, y2, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(h1, h2, rtol=5e-4, atol=5e-4)


# ------------------------------------------------------------------ CE

@pytest.mark.parametrize("T,D,V,bt,bv", [
    (100, 64, 1000, 32, 256), (256, 128, 511, 64, 128), (64, 32, 50, 16, 16)])
def test_ce_pallas_sweep(T, D, V, bt, bv):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (T, D), jnp.float32)
    w = jax.random.normal(ks[1], (V, D), jnp.float32) * 0.05
    t = jax.random.randint(ks[2], (T,), 0, V, jnp.int32)
    valid = (jnp.arange(T) % 3 != 0).astype(jnp.float32)
    a = cross_entropy_pallas(h, w, t, valid, block_t=bt, block_v=bv)
    b = cross_entropy_direct_ref(h, w, t, valid)
    np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(2, 80), v=st.integers(3, 300),
       seed=st.integers(0, 2**30))
def test_ce_blockwise_property(t, v, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (t, 16), jnp.float32)
    w = jax.random.normal(ks[1], (v, 16), jnp.float32) * 0.1
    tg = jax.random.randint(ks[2], (t,), 0, v, jnp.int32)
    a = cross_entropy_blockwise_ref(h, w, tg, block_v=32)
    b = cross_entropy_direct_ref(h, w, tg)
    np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5)
    # property: NLL >= 0 and >= log(1) trivially; also finite
    assert np.isfinite(float(a))
