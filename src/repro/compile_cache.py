"""Where the entry points keep JAX's persistent compilation cache.

A cold start on an accelerator compiles every program from scratch, and
the persistent cache is what lets a second process (or a second run of
the same checkout) skip that.  The cache directory is part of the key,
so it must not move between runs: it is either what the environment says
or one fixed path inside the repository, never a temp, pid or time name.

    from repro.compile_cache import enable_compile_cache
    cache_dir, n_entries = enable_compile_cache()   # first thing in main()
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache (listed in .gitignore): src/repro/compile_cache.py is
# two levels below the repository root.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> tuple[str, int]:
    """Turn on the persistent compilation cache; call before any compile.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it at import and
    that directory stands: no other is set.  Otherwise the cache goes to
    ``<repo>/.jax_cache``.

    Returns (cache directory, number of entries it held at the call).
    """
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    n_entries = len(os.listdir(path)) if os.path.isdir(path) else 0
    return path, n_entries
