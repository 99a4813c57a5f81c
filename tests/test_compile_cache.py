"""The entry points' persistent compilation cache (``repro.compile_cache``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, entries land there and nowhere
else; unset, the cache sits at the checkout's git-ignored ``.jax_cache``.
"""
import os
import subprocess
import sys
import textwrap

import jax

from repro.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_cache_dir_is_the_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_env_cache_dir_receives_every_entry(tmp_path):
    cache = tmp_path / "cache"
    code = textwrap.dedent("""
        from repro.compile_cache import use_compile_cache
        print(use_compile_cache())
        import jax, jax.numpy as jnp
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(4)).block_until_ready()
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"))
    listing = (lambda: sorted(os.listdir(DEFAULT_CACHE_DIR))
               if os.path.isdir(DEFAULT_CACHE_DIR) else [])
    default_before = listing()
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(cache)]
    assert any(f.endswith("-cache") for f in os.listdir(cache))
    assert listing() == default_before
