"""Records the small profiler trace ``tests/data/scoped.xplane.pb`` that
``test_scopes.py`` reduces: on the chip, three calls of the gradient of a
two-layer scan whose layer, a matrix product and a tanh under
``jax.named_scope("proj")``, is rematerialised (``jax.checkpoint``), all
inside a ``window`` span.  Its ops carry ``proj`` in their ``tf_op`` in
the forward pass, the backward pass and the recomputed forward pass.

    python3 chipbench/tests/record_scoped_trace.py <out.xplane.pb>
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def layer(x, w):
    with jax.named_scope("proj"):
        return jnp.tanh(x @ w)


def loss(ws, x):
    def body(h, w):
        return jax.checkpoint(layer)(h, w), None
    h, _ = jax.lax.scan(body, x, ws)
    return jnp.sum(h.astype(jnp.float32))


def main(out: str) -> int:
    f = jax.jit(jax.grad(loss))
    ws = jnp.full((2, 2048, 2048), 0.01, jnp.bfloat16)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(ws, x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("window"):
            time.sleep(0.01)
            for _ in range(3):
                f(ws, x).block_until_ready()
            time.sleep(0.01)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
