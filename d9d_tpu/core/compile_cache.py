"""Where the persistent XLA compilation cache lives.

Script entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
examples, the ``tools/bench_*.py`` mains) call :func:`enable_compile_cache` first
thing; ``import d9d_tpu`` never does, and the tests keep the cache off
(``tests/conftest.py``).

The directory is part of the cache key, so it has to be the same for
every process that should share compiled programs: either the one the
environment names, or one fixed path inside the checkout.

So is a program's metadata, here: jax leaves it out of the key by
default, and a hit then hands back the executable of whoever compiled
the same computation first, with THEIR ``jax.named_scope``s and module
paths on its ops. A device trace is read by those names
(``benchmarks/harness/layers.py``), so a program whose scopes changed
must not be served an executable that lacks them (PERF.md, PR 27: a
scope added to the MLA cache gather stayed invisible on the chip until
the program was compiled anew).
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
    directory from the environment and this sets no other. Otherwise the
    cache goes to :data:`DEFAULT_DIR`. Either way the key holds the
    program's metadata (scopes, source lines) beside its computation.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
