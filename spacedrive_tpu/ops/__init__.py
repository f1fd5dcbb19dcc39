"""TPU compute plane: batched content hashing, resizing, perceptual hashing."""

from __future__ import annotations

import os

#: the checkout-local compile cache, used when JAX_COMPILATION_CACHE_DIR
#: does not place it elsewhere. A fixed path on purpose: the directory
#: is part of the cache key, so one that moves (temp name, pid, home of
#: whoever runs) never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_CACHE_CONFIGURED = False


def configure_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache so the BLAKE3/resize/
    embed programs compile once per machine, not once per process (the
    wide-tile Pallas hash programs take minutes each to compile cold).

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it by itself and
    no directory is set in code; otherwise the cache is
    `<checkout>/.jax_cache`. Returns the directory in effect. Safe to
    call repeatedly; the first call wins."""
    global _CACHE_CONFIGURED
    import jax

    if not _CACHE_CONFIGURED:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        _CACHE_CONFIGURED = True
    return jax.config.jax_compilation_cache_dir
