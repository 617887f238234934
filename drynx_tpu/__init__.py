"""drynx_tpu — TPU-native decentralized, privacy-preserving, verifiable
statistical-query and ML-training framework (capabilities of cgrigis/drynx,
re-designed for JAX/XLA/Pallas/pjit).

64-bit integer support is required for exact statistics vectors and limb
packing (the crypto path itself is pure uint32 limb math); float kernels in
the training path explicitly request float32/bfloat16, so enabling x64 here
does not put float64 on the TPU hot path.
"""
import os

# Opt-in runtime lock-order recorder (analysis/locktrace.py): patch
# threading.Lock/RLock BEFORE anything in this package creates one, so
# every named_lock in the tree is traced. The chaos cross-check in
# tests/test_concurrency_analysis.py runs a real server drain under this
# and asserts observed acquisition order ⊆ the static lock-order graph.
if os.environ.get("DRYNX_LOCK_TRACE", "0") == "1":
    from .analysis import locktrace as _locktrace
    _locktrace.install()

# Opt-in runtime determinism recorder (analysis/dettrace.py): arm it
# BEFORE any byte-identity sink (ProofDB.put, transcript serialization,
# journal appends) can fire, so every write of the process is hashed.
# The chaos cross-check in tests/test_determinism_analysis.py runs the
# same proofs-on survey twice with one seed under this and asserts the
# per-sink write multisets are identical — the dynamic half of the
# static nondeterminism-taint pass (analysis/determinism.py).
if os.environ.get("DRYNX_DET_TRACE", "0") == "1":
    from .analysis import dettrace as _dettrace
    _dettrace.install()

# Opt-in runtime protocol recorder (analysis/prototrace.py): arm it
# BEFORE any resource lifecycle (pool slab consumption, ConnPool
# checkouts, pane seals, checkpoint saves) can fire, so every
# instance's event sequence is captured from creation. The chaos
# cross-check in tests/test_typestate_analysis.py drives a proofs-on
# survey plus a pool consume/crash-recover cycle under this and
# asserts every observed sequence is accepted by the declared automata
# — the dynamic half of the static typestate pass (analysis/typestate.py).
if os.environ.get("DRYNX_PROTO_TRACE", "0") == "1":
    from .analysis import prototrace as _prototrace
    _prototrace.install()

# Lint-only fast path: the static analyzer (python -m drynx_tpu.analysis)
# is deliberately jax-free, but importing its parent package triggers
# ~0.4s of accelerator setup below. DRYNX_SKIP_JAX_INIT=1 skips ALL of it
# — only safe for processes that never execute jax code (the pre-commit
# lint tier in scripts/check.sh sets it).
if os.environ.get("DRYNX_SKIP_JAX_INIT", "0") == "1":
    jax = None
else:
    import jax

if jax is not None:
    jax.config.update("jax_enable_x64", True)

# Serialize XLA compiles process-wide AND run each on a dedicated
# fresh-stacked thread. Two reasons, both observed killing processes:
#   1. Two Python threads entering XLA's CPU backend_compile concurrently
#      segfault/abort the compiler under load (this framework is
#      deliberately multi-threaded at the service layer — VN verifiers,
#      proof threads, TCP handlers).
#   2. Even a SINGLE compile segfaults in a long-lived process once the
#      MAIN thread's stack has grown into an adjacent mapping (XLA's CPU
#      pipeline recurses deeply on the crypto graphs; pytest workers died
#      mid-suite on compiles that pass in isolation).
# Running the compile on a fresh thread with an explicit 512 MB stack gives
# every compile a clean, collision-free stack; the lock keeps them one at a
# time. Compiles are rare and cached — the thread spawn is noise.
# Kill-switch: DRYNX_NO_COMPILE_LOCK=1.
if jax is not None and os.environ.get("DRYNX_NO_COMPILE_LOCK", "0") != "1":
    try:
        import threading as _threading

        from jax._src import compiler as _jax_compiler

        from .resilience.policy import named_lock as _named_lock

        _orig_bcl = _jax_compiler.backend_compile_and_load
        _compile_lock = _named_lock("compile_lock")
        _COMPILE_STACK = 512 * 1024 * 1024

        def _locked_backend_compile(*args, **kwargs):
            with _compile_lock:
                box: dict = {}

                def run():
                    try:
                        box["v"] = _orig_bcl(*args, **kwargs)
                    except BaseException as e:   # re-raised on the caller
                        box["e"] = e

                old = _threading.stack_size(_COMPILE_STACK)
                try:
                    t = _threading.Thread(target=run, name="drynx-compile")
                    t.start()
                finally:
                    _threading.stack_size(old)
                t.join()
                if "e" in box:
                    raise box["e"]
                return box["v"]

        _jax_compiler.backend_compile_and_load = _locked_backend_compile
    except Exception:   # jax internals moved: lose the guard, not the app
        pass
