"""Where the entry points keep the persistent compile cache.

Each case runs in a fresh interpreter: turning the cache on is
process-wide, and no other test should write to it.
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.compile_cache import CHECKOUT_CACHE_DIR

SRC = Path(__file__).resolve().parents[1] / "src"

COMPILE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
"""


def _run(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, (str(SRC), env.get("PYTHONPATH"))))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", COMPILE.format(compile=compile_)], env=env,
        capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.split()


def test_environment_directory_is_used_and_nothing_else(tmp_path):
    cache = tmp_path / "jax-cache"
    returned, configured = _run(cache, True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir()), "nothing was cached in the given directory"


def test_checkout_directory_without_environment():
    returned, configured = _run(None, False)
    assert returned == configured == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR.parent == SRC.parent
    assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
