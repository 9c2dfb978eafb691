"""Where compiled XLA programs are kept between process starts."""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache so a process restart does
    not re-pay XLA compile time for shapes it has already seen (the 5k×50k
    lattice compiles for minutes cold). One rule for the directory: where
    JAX_COMPILATION_CACHE_DIR is set, jax already reads it and nothing is set
    here; otherwise the fixed `<checkout>/.cache/xla` — the path is part of
    the cache key, so it never moves. Call before the first compile of the
    process (every entry point that builds a scheduler does); idempotent.
    Returns the directory in effect."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        d = os.path.join(_CHECKOUT, ".cache", "xla")
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    # cache every compile that takes noticeable time, not just >1s ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir
