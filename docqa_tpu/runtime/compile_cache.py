"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` decides it when set (JAX reads that
variable itself — the program then sets nothing), otherwise it is ONE
fixed directory inside the checkout.  Called at the process entry point
only (``service.app.serve``), never at import, so a test that counts
compiles still sees real ones.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")


def compile_cache_dir() -> str:
    """The directory the cache is (or will be) kept in.  Standard
    library only: a parent process that must stay off JAX can ask."""
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


def configure_compile_cache() -> str:
    """Point JAX at the cache directory for this process and return it.
    With ``JAX_COMPILATION_CACHE_DIR`` set this changes nothing."""
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return compile_cache_dir()
