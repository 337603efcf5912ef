"""Where the persistent compilation cache lands (``repro.compile_cache``).

Each case runs in a fresh interpreter: JAX reads its cache settings once,
before the first compile, so they cannot be switched inside a test process.
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.compile_cache import DEFAULT_CACHE_DIR

_SNIPPET = r"""
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print("DIR", enable_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda a: jnp.sin(a) @ a.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _SNIPPET.format(compile=compile_)],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_cache_follows_the_environment_variable(tmp_path):
    cache = tmp_path / "jax-cache"
    got = _run(cache, compile_=True)
    assert got["DIR"] == got["CONFIG"] == str(cache)
    assert any(cache.iterdir()), "no compiled program was cached"


def test_cache_defaults_to_a_fixed_directory_in_the_checkout():
    got = _run(None, compile_=False)
    assert got["DIR"] == got["CONFIG"] == str(DEFAULT_CACHE_DIR)
    root = Path(__file__).resolve().parents[1]
    assert DEFAULT_CACHE_DIR == root / ".jax_cache"
