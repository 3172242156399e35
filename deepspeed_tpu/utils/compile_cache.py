"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``chip_smoke.py``, ``bin/ds_bench``,
``autotuning/trial_worker.py``, ``inference/fleet_worker.py``).

The directory is part of the cache key, so processes that should share
compilations must agree on it and it must not move between runs: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no directory is
set in code; otherwise the cache is one fixed directory inside the checkout
(git-ignored) — never one built from ``~``, a temporary name, a pid or the
time.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; call before the first compilation.
    Returns the directory in use."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
