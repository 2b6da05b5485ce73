"""Process-level runtime settings shared by the repo's entry scripts.

Only the persistent compilation cache lives here: where it is kept is
part of its key, so every script that compiles on the chip
(``chip_smoke.py``, ``bench/run.py``) resolves it the same way.
"""

from __future__ import annotations

import os

import jax


def use_compile_cache(root: str) -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is configured.  Otherwise the cache is ``<root>/.jax_cache``
    — a fixed path, never a temporary one, so a later run in the same
    checkout finds the entries.  Call before the first compile.  Returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
