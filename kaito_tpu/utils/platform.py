"""Persistent XLA compile cache for the entry points.

The engine compiles one program per prefill bucket, pack size and
fused-decode depth, and a pod (or a chip run) that restarts pays all of
them again unless the compiled executables persist.  Every standalone
entry point (serving server, tuning CLI, RAG service, the chip smoke's
kernel child) calls :func:`enable_compile_cache` first
thing in its ``main()`` — never at package import, so importing
``kaito_tpu`` configures nothing.

Placement comes from outside: when ``JAX_COMPILATION_CACHE_DIR`` is set
JAX reads it by itself and this sets nothing.  Otherwise the cache goes
to ``.jax_cache`` beside the package — a fixed path, because the path
is part of the cache key and a directory that moves never hits.
"""

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
