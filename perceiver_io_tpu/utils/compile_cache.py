"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles on the chip (the family CLIs'
``main``, ``chip_smoke.py``, ``benchmarks/run.py``):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and the code sets nothing; where it is not, the cache goes to one fixed,
git-ignored directory at the root of the checkout. The directory's path is
part of the cache's key, so a directory that moves between runs never hits.
The test suite turns the cache off (``JAX_ENABLE_COMPILATION_CACHE=false``,
``tests/conftest.py``), which also covers the processes it starts.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed default: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory. Call
    before the first compile; does not initialize a backend."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
