"""Where the persistent compilation cache lands (repro.common.jaxcache)."""
import os
import subprocess
import sys

from repro.common.jaxcache import CHECKOUT_CACHE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import jax, jax.numpy as jnp
from repro.common.jaxcache import enable_compile_cache
print(enable_compile_cache())
jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)))
"""


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu", JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_follows_the_environment(tmp_path):
    assert _run(tmp_path) == str(tmp_path)
    assert any(name.startswith("jit_") for name in os.listdir(tmp_path))


def test_cache_defaults_to_a_fixed_ignored_checkout_dir():
    assert str(CHECKOUT_CACHE_DIR) == os.path.join(ROOT, ".jax_cache")
    assert _run(None) == str(CHECKOUT_CACHE_DIR)
    assert any(n.startswith("jit_") for n in os.listdir(CHECKOUT_CACHE_DIR))
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
