"""HLO cost walker: exact FLOPs on known programs, while-loop trip
multiplication, collective accounting."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_single_matmul_flops():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    c = _compile(lambda a, b: a @ b, x, w)
    res = hlo_cost.analyze(c.as_text())
    assert res["flops"] == 2 * 128 * 256 * 64


def test_scan_multiplies_by_trip_count():
    def f(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    for n in (1, 4, 9):
        ws = jax.ShapeDtypeStruct((n, 64, 64), jnp.float32)
        c = _compile(f, x, ws)
        res = hlo_cost.analyze(c.as_text())
        assert res["flops"] == n * 2 * 64 * 64 * 64, n
        # XLA's own analysis counts the body once — that's the bug we fix
        if n > 1:
            assert c.cost_analysis()["flops"] < res["flops"]


def test_nested_scan():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, wi):
                return ci @ wi, None
            y, _ = jax.lax.scan(inner, c, w)
            return y, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 5, 32, 32), jnp.float32)
    c = _compile(f, x, ws)
    res = hlo_cost.analyze(c.as_text())
    assert res["flops"] == 15 * 2 * 32 ** 3


def test_collective_bytes_counted():
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run under dryrun env)")


def test_bytes_nonzero_and_sane():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    w = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    c = _compile(lambda a, b: a @ b, x, w)
    res = hlo_cost.analyze(c.as_text())
    # dot reads 2x4MB and writes 4MB
    assert 12e6 <= res["hbm_bytes"] <= 20e6
