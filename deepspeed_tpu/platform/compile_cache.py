"""Where compiled programs persist between processes.

One function, called before the first compile by everything that is
meant to run on the chip (chip_smoke.py first). The directory is part
of the cache key's environment, so it never moves: whoever runs the
program may place it with `JAX_COMPILATION_CACHE_DIR` (JAX reads that
variable itself — nothing is set in code then); otherwise it is the
fixed `<checkout>/.jax_cache` (git-ignored). Never a tempfile, a pid or
a timestamp. The CPU test lane does not call this.
"""

import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
