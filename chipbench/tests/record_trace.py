"""Records the small profiler trace ``tests/data/small.xplane.pb`` that
``test_trace.py`` reduces: on the chip, three calls of a matrix product
inside a ``window`` span, each call under a ``dispatch`` span, with host
sleeps around them that leave the device idle.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("window"):
            # the device's clock in the trace can sit a millisecond or so
            # off the host's: keep the calls well inside the window
            time.sleep(0.01)
            for _ in range(3):
                with jax.profiler.TraceAnnotation("dispatch"):
                    y = f(x)
                y.block_until_ready()
                with jax.profiler.TraceAnnotation("token_read"):
                    time.sleep(0.01)
            time.sleep(0.01)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
