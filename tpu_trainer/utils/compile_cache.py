"""Where compiled programs are kept between runs.

Every entry point that compiles calls :func:`enable_compile_cache` first
thing. The directory is part of the cache key's story: one that moves
(temp name, pid, timestamp) never hits, so it is either wherever
``JAX_COMPILATION_CACHE_DIR`` says — jax reads that variable itself and
nothing is set in code — or ONE fixed directory inside the checkout.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored), next to the tpu_trainer package.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def _pinned_to_cpu() -> bool:
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def enable_compile_cache() -> str:
    """Place jax's persistent compilation cache; return its directory.

    A process pinned to the CPU (``JAX_PLATFORMS=cpu``, ``--device cpu``,
    the test suite) gets the path but no cache: a CPU program compiles in
    seconds, and XLA:CPU's loader logs a page of machine-feature errors
    for every entry it reads back.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    if not _pinned_to_cpu():
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
