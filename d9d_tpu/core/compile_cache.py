"""Where the persistent XLA compilation cache lives.

Script entry points (``chip_smoke.py``, ``bench.py``, the examples, the
``tools/bench_*.py`` mains) call :func:`enable_compile_cache` first
thing; ``import d9d_tpu`` never does, and the tests keep the cache off
(``tests/conftest.py``).

The directory is part of the cache key, so it has to be the same for
every process that should share compiled programs: either the one the
environment names, or one fixed path inside the checkout.
"""

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_compile_cache (listed in .gitignore): derived from this
# file's location alone, never from a temp dir, a pid or a clock, so two
# processes of one checkout always agree on it
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax has already taken that
    directory from the environment and this sets nothing. Otherwise the
    cache goes to :data:`DEFAULT_DIR`.
    """
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
