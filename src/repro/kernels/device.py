"""Host-side plumbing shared by the store kernels: which backend runs, the
shape ladder that bounds how many programs get compiled, and the
persistent compile cache.

``jax`` is imported lazily so the numpy store path never loads it.
"""
from __future__ import annotations

import os
import pathlib


def require_tpu() -> None:
    """Compiled kernels run on a TPU only; there is no silent fallback."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"compiled Pallas kernels need a TPU, but JAX runs on "
            f"{platform!r}; use the 'interpret' or 'numpy' backend here")


def bucket(n: int, tile: int) -> int:
    """Smallest ``tile * 2**k`` holding ``n`` items.  Padding every kernel
    operand up this ladder keeps the programs compiled per kernel to a few
    dozen instead of one per distinct shape."""
    return tile << ((max(n, 1) - 1) // tile).bit_length()


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else at ``<checkout>/.jax_cache``.  A cache
    is found again only at the same path, so the path is fixed."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
