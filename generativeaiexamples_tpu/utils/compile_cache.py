"""One persistent XLA compile cache for every entry point.

A cold start compiles the whole prefill-bucket x decode-rung ladder;
the cache turns every later start into disk reads. Its directory is
part of each entry's key, so it must not move: it is either the one the
operator placed from outside (``JAX_COMPILATION_CACHE_DIR``, which JAX
reads itself — then no directory is set in code) or ONE fixed path
inside the checkout. Never a path derived from a model name, a
checkpoint hash, a pid or a temp name: a directory that changes never
hits.
"""

from __future__ import annotations

import os

from .logging import get_logger

logger = get_logger(__name__)

# <checkout>/.jax_cache (git-ignored).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    force ("" = none). Call before the first engine or encoder compiles.

    XLA:CPU results encode the build host's exact machine features, so a
    persistent CPU cache poisons runs on any other host: on a CPU
    backend nothing is enabled here (an operator who sets
    ``JAX_COMPILATION_CACHE_DIR`` anyway has asked JAX for it directly).
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        logger.info("compile cache: %s (JAX_COMPILATION_CACHE_DIR)", placed)
        return placed
    if jax.default_backend() == "cpu":
        return ""
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    logger.info("compile cache: %s", DEFAULT_DIR)
    return DEFAULT_DIR
