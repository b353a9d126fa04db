"""Where the persistent compilation cache goes (``repro.launch.compile_cache``)."""

import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_dir_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.use_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_entries_land_only_in_the_env_dir(tmp_path):
    """A compile after the helper writes its entry where the variable says."""
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(use_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8)).block_until_ready()\n"
    )
    default = os.path.join(REPO, ".jax_cache")
    listing = sorted(os.listdir(default)) if os.path.isdir(default) else None
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"), "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path / "cache")
    assert os.listdir(tmp_path / "cache")
    assert (sorted(os.listdir(default)) if os.path.isdir(default) else None) == listing
