"""The persistent compile cache: JAX_COMPILATION_CACHE_DIR wins, else a
fixed git-ignored ``.jax_cache/`` at the checkout root."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.configure_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # same answer every call: no pid, clock or temporary name in it
        assert compile_cache.configure_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_env_dir_wins_and_receives_the_cache(tmp_path):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.compile_cache import configure_compile_cache
        print(configure_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir()), "no cache entry written"
