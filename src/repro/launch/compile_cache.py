"""Where the entry points keep JAX's persistent compilation cache.

``launch.serve``, ``chip_smoke.py`` and full-width worker hosts keep
compiled programs on disk, so a cold start does not recompile every
full-width program. The directory comes from outside when
``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads that variable itself, and
nothing here overrides it); otherwise it is the fixed
``<repo>/.jax_cache``. The path is part of each
entry's key, so it never contains a temporary name, a process id or a
time: a run finds what an earlier run from the same checkout compiled.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory the entry points use under ``environ``
    (default: this process's environment)."""
    env = os.environ if environ is None else environ
    return env.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`cache_dir` and return it.
    Call before the first compile."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
