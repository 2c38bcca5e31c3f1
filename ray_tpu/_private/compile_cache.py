"""Where JAX's persistent compilation cache lives.

A cold process compiles every program from nothing, which on the chip is
a large part of a short run. The cache's directory is part of what a run
can rely on, so there is one rule for it, used by ``chip_smoke.py``,
``benchmark/run.py`` and every child the runtime starts with a chip: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing is set in
code; otherwise the cache sits at a fixed path inside the checkout,
derived from this package's location (a directory that moves never
hits).
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (git-ignored).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Point this process's JAX at the persistent cache; returns its
    directory. Call before the first compilation."""
    configured = os.environ.get(_ENV)
    if configured:
        return configured  # JAX reads the variable itself
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def child_env(env: dict) -> None:
    """The same rule for a child that will own a chip, through the
    environment it is spawned with: its JAX reads the variable itself,
    so a child that never compiles never imports jax for this."""
    if not env.get(_ENV):
        env[_ENV] = DEFAULT_DIR
