"""The one rule for jax's persistent compilation cache.

If JAX_COMPILATION_CACHE_DIR is set, that directory is the cache: jax reads
the variable itself and this code sets nothing. If it is not set, the cache
is `<checkout>/.jax_cache`, a fixed path (the path is part of the cache key,
so a directory that moves never hits). Every entry point that wants the
cache calls `enable_compilation_cache()`; nothing else in the repo writes
`jax_compilation_cache_dir`. The CPU test tier sets the variable itself
(tests/conftest.py, `.jax_cache_tests`).

What the cache saves is XLA/Mosaic compile time. Tracing and lowering of
the trace-time-unrolled limb kernels run before the cache lookup, so jax's
cache cannot save them. For the stored programs of a survey
(`service._fused_enc/_agg/_ks/_dec`, `parallel/dro.PROGRAMS`) the
executable store does
(utils/exec_store.py): serialised executables under
`<this directory>/exec_store/`, one file per program and shape, keyed
before any tracing by the call's abstract arguments, a digest of the
package's source and the versions and flags underneath. It engages where
this directory is configured and the backend is a TPU; to clear it, delete
that sub-directory. Every other program of a process still traces and
lowers in every process.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent cache on by the rule above; returns its
    directory. Safe to call more than once."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["enable_compilation_cache", "ENV_VAR", "DEFAULT_DIR"]
