"""Compile-cache placement for the entry points
(``repro.runtime.compile_cache``)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parent.parent


def test_env_dir_is_used_as_is(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert compile_cache.compile_cache_dir() == str(tmp_path)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no directory
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          threshold)


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert Path(first) == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_entry_point_sets_the_default_dir():
    # in a child, so this test process keeps its cache policy untouched
    code = textwrap.dedent("""
        import jax
        from repro.runtime.compile_cache import enable_compile_cache
        print(enable_compile_cache() == jax.config.jax_compilation_cache_dir
              and jax.config.jax_persistent_cache_min_compile_time_secs == 0)
    """)
    env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "True", out.stderr
