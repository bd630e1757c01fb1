"""Where JAX's persistent compilation cache lives.

A restarted worker (and every process of one job) should compile from
cache, and the directory is part of the cache key's context: a path
that moves never hits. So the placement rule is the whole module:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  set in code.
- otherwise: one fixed, git-ignored directory inside the checkout —
  no pid, time or tempfile name in the path.
"""

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """The directory the persistent cache uses in this environment."""
    return os.environ.get(CACHE_DIR_ENV) or _CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Point JAX at ``compile_cache_dir()``; call before the first
    compile. Returns the directory in use."""
    if not os.environ.get(CACHE_DIR_ENV):
        import jax

        jax.config.update(
            "jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR
        )
    return compile_cache_dir()
