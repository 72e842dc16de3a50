"""Where the entry points keep JAX's persistent compilation cache.  Each
case runs in a child process on the CPU backend: the cache directory is
process-wide JAX state."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(ROOT, ".jax_cache")

CHILD = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def _listing(d):
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def _child(env_dir, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", CHILD, mode], env=env,
                       cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(tmp_path, env_set):
    if env_set:
        env_dir = str(tmp_path / "cache")
        before = _listing(CHECKOUT_CACHE)
        used, configured = _child(env_dir, "compile")
        assert used == configured == env_dir
        assert _listing(env_dir), "no cache entry written"
        assert _listing(CHECKOUT_CACHE) == before
    else:
        # report only: a test must not write into the checkout
        used, configured = _child(None, "report")
        assert used == configured == CHECKOUT_CACHE
