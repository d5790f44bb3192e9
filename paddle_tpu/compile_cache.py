"""Persistent XLA compile cache, placeable from outside.

Every process entry point (`cli.main`, `chip_smoke.py`)
calls `enable()` once before its first compile; importing `paddle_tpu`
never does. Directory rule — the path is part of the cache key, so it
must not move between runs:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing is set
  in code.
- unset: `<checkout>/.jax_cache` (git-ignored), so the children one
  parent starts share it without any plumbing.

JAX's own thresholds stay (programs that compile in under a second are
not written): the cold cost lives in the whole-step programs.
"""

from __future__ import annotations

import os

_FIXED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory.
    Touches jax.config only — no backend is initialised."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _FIXED_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
