"""Where the persistent XLA compile cache lives — decided in ONE place.

Every entry point that compiles real programs (``chip_smoke.py``,
``bench.py``, ``tools/serve_bench.py``, the replica process
``python -m paddle_tpu.serving.remote``, the ``experiments/exp_*.py``
scripts) calls :func:`use_compile_cache` before its first compile, so
the processes of one command share what they compile and a second run
on the same machine starts warm.

The directory is part of the cache key's meaning: a cache that moves
never hits. So the rule is fixed: whoever runs the program may place
the cache with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable
itself, and then nothing is set in code); otherwise it is
``<checkout>/.jax_cache``, the same path from every process of the
checkout. ``.gitignore`` lists it: compiled programs are made at run
time, never committed.
"""
from __future__ import annotations

import os

__all__ = ["use_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return
    it. With ``JAX_COMPILATION_CACHE_DIR`` set this sets nothing."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
