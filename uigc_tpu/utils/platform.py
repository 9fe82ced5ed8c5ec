"""Platform helpers for entry points.

JAX picks the platform itself: the TPU where one is attached, whatever
``JAX_PLATFORMS`` names otherwise (the CPU test tier sets ``cpu``).
Nothing here overrides that choice or falls back from it — an entry
point that needs the chip asks :func:`is_tpu_platform` and fails.
"""

from __future__ import annotations

import os

#: The compile cache's fixed home inside the checkout (git-ignored).
#: The directory is part of every cache key, so it must never be built
#: from a temp dir, pid, uid or time — a path that moves never hits.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def is_tpu_platform(name: str) -> bool:
    """True when a ``jax.Device.platform`` value is a TPU chip."""
    return name == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no other directory is set in code; where it is not, the cache lives
    in one fixed directory inside the checkout.  Call before the first
    compile (entry points do so right after ``import jax``)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
