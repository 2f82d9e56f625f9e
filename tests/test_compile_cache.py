"""Placement of JAX's persistent compilation cache (launch/compile_cache).

Each case runs in a fresh interpreter: the cache directory is read once,
at a process's first compile."""
import os
import subprocess
import sys
import uuid

import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * float(sys.argv[1]))(jnp.ones(8)).block_until_ready()
"""


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, env_set):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(tmp_path / "cache") if env_set else CHECKOUT_CACHE
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    assert CHECKOUT_CACHE == os.path.join(ROOT, ".jax_cache")
    before = _entries(CHECKOUT_CACHE)
    tag = str(1 + uuid.uuid4().int % 10 ** 6)     # a program never cached
    out = subprocess.run([sys.executable, "-c", SCRIPT, tag], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == want
    new_in_checkout = _entries(CHECKOUT_CACHE) - before
    if env_set:
        assert any(n.startswith("jit_") for n in _entries(want))
        assert not new_in_checkout
    else:
        assert any(n.startswith("jit_") for n in new_in_checkout)
        for n in new_in_checkout:                 # leave the cache as found
            os.remove(os.path.join(CHECKOUT_CACHE, n))
