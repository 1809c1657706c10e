"""Where the persistent XLA compilation cache lives.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` into
``jax.config.jax_compilation_cache_dir`` by itself. Where whoever starts the
process set it, the cache is there and this module touches nothing.
Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path, because
processes that should share compiled programs (two runs of
``chip_smoke.py`` in one chip-tool command, the stage children of
``bench.py``) must agree on it without talking to each other.

Called by the process entry points (``chip_smoke.py``, ``bench.py --stage``,
the CLI mains), never at import time.

JAX writes only programs that took ``jax_persistent_cache_min_compile_time_secs``
(1.0 s) to compile. On the v5e that leaves out most of what this repository
compiles: of the 86 programs of a ``chip_smoke.py`` run, 79 compiled in under
a second, among them the decode step and every short prefill bucket (PR 21
chip run). A cache this module places therefore keeps every program; a cache
placed from outside keeps whatever its owner configured.
"""

from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

__all__ = ["ensure_compile_cache"]


def ensure_compile_cache() -> str:
    """Return the compile-cache directory, placing it under the checkout
    unless one is already configured. Call before the first compilation:
    what compiled earlier is neither looked up nor written, and once JAX
    has opened a cache directory it ignores a later change."""
    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
