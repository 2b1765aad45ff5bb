"""Where XLA's persistent compilation cache lives.

Every program the dict path runs is keyed on a shape bucket (the feed on
``n_pad``, the closes on ``(n_fetch, width, n_over_buf[, n_blk_buf])``),
so a cold process compiles each bucket it meets — seconds apiece on a
TPU — and a restarted agent would pay them all again. JAX can keep the
compiled binaries on disk; this module decides where, once, for every
entry point (the CLI, chip_smoke.py's children).

The directory is part of the cache key, so it must not move between
runs: it is placed from outside with ``JAX_COMPILATION_CACHE_DIR`` (JAX
reads the variable itself — no code sets anything then), and otherwise
sits at a fixed path inside the checkout. Never a temp dir, a platform
name, a pid or a time: any of those makes a directory that never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — the same path from every process."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure() -> str:
    """Point JAX at the persistent compile cache and return its
    directory. Call before the first JAX computation; importing jax and
    updating its config initialises no backend."""
    import jax

    # Keep every program, not only the slow ones (JAX's default skips
    # compiles under a second): the agent's program set is small and
    # bounded, and the sub-second ones are exactly what a 3 s streaming
    # feed budget cannot afford to recompile.
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
