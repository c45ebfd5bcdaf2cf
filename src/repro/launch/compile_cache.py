"""Where the entry points keep jax's persistent compilation cache."""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory.  A fixed path: a later run of the
#: same checkout finds what an earlier one compiled, which a temporary or
#: per-run name would never do.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and is
    left alone; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
