"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

import jax

#: The fixed in-checkout cache directory. The path is part of each entry's
#: key, so it must not move between runs (no temporary or per-process path).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Call it first in an entry point's ``main()``, never at import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and that
    directory stays the only one. Otherwise the cache goes to ``CACHE_DIR``.
    Every program is cached however fast it compiled, so a second run of
    the same command compiles nothing it ran before."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
