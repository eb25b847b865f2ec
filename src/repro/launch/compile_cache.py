"""JAX's persistent compilation cache, placed from outside or at one
fixed path inside the checkout.

A cold run of a full-width model compiles one prefill program per
bucket plus the decode scan; the persistent cache lets the next process
that builds the same programs load them instead.  The directory is part
of what makes a later run find an entry, so it never derives from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed in-checkout cache directory (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory; otherwise the cache goes to
    ``REPO_CACHE_DIR``."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
