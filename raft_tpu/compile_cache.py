"""Where JAX's persistent compilation cache lives.

One rule, applied first thing by every entry point that compiles for the
chip (``raft_tpu.cli.main``, ``chip_smoke.py``, the chip-side
``tools/`` scripts): when ``JAX_COMPILATION_CACHE_DIR`` is set the caller
has placed the cache and JAX reads the variable itself — nothing is set in
code; otherwise the cache is ``<checkout>/.jax_cache``, resolved from this
package's own location.  The path is part of the cache key, so it is fixed:
never a temp name, a pid or a time.

This is NOT the AOT engine cache (``--engine-cache-dir``,
serving/aot_cache.py), which stores serialized executables under an
explicit flag of its own.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — the same path from any working directory.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in
    use.  Touches only ``jax.config`` — no backend is initialised."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
