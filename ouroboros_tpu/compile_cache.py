"""Where compiled programs and measured tables are kept: one rule.

The ladder kernels take minutes to compile per window shape, so every
device path keeps JAX's persistent compilation cache on, and the
verify service's break-even table (crypto/batching.py) sits in the
same directory: it was measured against those programs.

The rule, in `cache_dir()` and nowhere else:

- `JAX_COMPILATION_CACHE_DIR` set: JAX itself reads it at import; this
  code uses that directory for its own files and configures nothing.
- not set: one fixed directory inside the checkout (git-ignored).  The
  path is part of what a later process must find again, so it is never
  the temp dir and carries no pid or time.

Code never writes the environment variable.  This module imports
without JAX (host-only tooling reads the paths); `cache_dir()` imports
it only when it has to point JAX somewhere.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def cache_dir() -> str:
    """The cache directory in effect, created if missing.  Safe to call
    repeatedly; every caller gets the same answer for the same
    environment."""
    d = os.environ.get(ENV_VAR)
    if not d:
        d = DEFAULT_DIR
        import jax
        if jax.config.jax_compilation_cache_dir != d:
            jax.config.update("jax_compilation_cache_dir", d)
            # 0, not the default 1.0: the tests' and dryrun's small
            # shapes compile in under a second each but number in the
            # hundreds, and would otherwise recompile in every process
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)
    os.makedirs(d, exist_ok=True)
    return d
