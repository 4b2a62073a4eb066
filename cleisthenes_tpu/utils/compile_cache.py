"""Where XLA's persistent compilation cache lives.

The XLA path compiles one program per (kernel, shape bucket): seconds
each for the Montgomery exponent loops and per SHA-256 message length.
A process that starts with no cache pays all of it again, so every
entry script calls ``enable_compile_cache()`` before its first
compilation.

The directory is chosen from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and this
helper sets nothing.  Otherwise the cache goes to one fixed directory
inside the checkout (``.jax_cache/``, git-ignored).  The path is part
of the cache key, so it never contains a tempdir, pid or timestamp.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring) and return the directory in effect."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(jax.config.jax_compilation_cache_dir)


__all__ = ["DEFAULT_DIR", "ENV_VAR", "enable_compile_cache"]
