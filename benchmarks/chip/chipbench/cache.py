"""JAX's persistent compilation cache at a fixed path inside the checkout."""
from __future__ import annotations

import os
import pathlib


def configure(root: pathlib.Path) -> None:
    """Keep every compiled program in ``<root>/.jax_cache``, so that only
    the first run of a cell in a checkout compiles.  Call before JAX
    compiles anything; the program takes the directory from the
    environment."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: it reads an access-time file beside every entry and
    # fails every write once one is missing.
    jax.config.update("jax_compilation_cache_max_size", -1)
