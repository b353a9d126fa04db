"""The harness's runs in these tests keep their compile cache in a directory
of the test session's own, not in the checkout's ``.jax_cache/``, which the
benchmark's runs on the chip fill and other tests watch."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from chipbench import harness  # noqa: E402


@pytest.fixture(autouse=True)
def own_compile_cache(monkeypatch, tmp_path_factory):
    real = harness.setup_jax
    cache = tmp_path_factory.getbasetemp() / "jax_cache"

    def setup_jax():
        real()
        jax.config.update("jax_compilation_cache_dir", str(cache))

    monkeypatch.setattr(harness, "setup_jax", setup_jax)
