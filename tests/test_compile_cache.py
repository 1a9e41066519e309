"""Where the entry points' persistent compilation cache lives."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    print(enable_compile_cache())
    jax.jit(lambda x: jnp.tanh(x @ x) + 1)(jnp.ones((64, 64))).block_until_ready()
""")


def _files(path) -> set:
    return {os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs}


def test_env_dir_holds_every_compiled_program(tmp_path):
    target = tmp_path / "cache"
    checkout_before = _files(compile_cache.CHECKOUT_CACHE_DIR)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(target),
               JAX_PLATFORMS="cpu",
               # cache even a tiny, fast program
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", COMPILE], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    assert out.stdout.strip() == str(target)
    assert _files(target), "nothing compiled into JAX_COMPILATION_CACHE_DIR"
    assert _files(compile_cache.CHECKOUT_CACHE_DIR) == checkout_before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
