"""Where the persistent XLA compilation cache lives.

One rule, for every entry point that compiles at real size
(chip_smoke.py, benchmark/run.py, the examples): where the environment names a
directory (``JAX_COMPILATION_CACHE_DIR``), JAX has already read it and
this module sets none in code; otherwise the cache is
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
the cache key and a directory that moves (a temporary, a pid, a date)
never hits.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
