"""Where the persistent jax compilation cache lives.

One rule for every entry point (the sweep CLI, the sweep server, the
measurement runner, the benchmarks and ``chip_smoke.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads it itself; the program
  sets no location of its own;
* unset — ``<checkout>/.jax_cache``, a fixed, gitignored directory of
  the checkout.  The path is part of the cache key, so it never names
  a temp directory, a pid or a time, and it never leaves the checkout.

CPU rehearsals point the variable outside the tree, so a copy of the
checkout sent to a chip host stays small.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compilation_cache_dir(env: Mapping[str, str] | None = None) -> Path | None:
    """The directory the program itself must configure: ``None`` when
    ``JAX_COMPILATION_CACHE_DIR`` is set in ``env`` (default
    ``os.environ``) and jax places the cache, else
    :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ if env is None else env
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return CHECKOUT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Turn the persistent cache on under the rule above, caching every
    executable however small or quick to compile, and return the
    directory in use."""
    import jax

    cache_dir = compilation_cache_dir()
    if cache_dir is not None:
        cache_dir.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
