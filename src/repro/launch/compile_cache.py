"""Where JAX keeps its persistent compilation cache.

Entry points (``train.main``, ``serve.main``, ``chip_smoke.py``) call
:func:`use_compile_cache` once before their first compile; importing this
module changes nothing.
"""

from __future__ import annotations

import os

import jax

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: fixed,
#: so a later run of the same checkout finds what an earlier one compiled.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and is
    left as it is; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
