"""Test harness config: force a virtual 8-device CPU mesh before JAX use.

Mirrors the reference's in-process multi-node test strategy (onet LocalTest,
reference: services/service_test.go:29-66) — multi-"node" here means multiple
XLA host devices so sharding/collective paths run for real without TPUs.

The tests run on the CPU whatever the caller's JAX_PLATFORMS says: the
variable is overwritten here and jax.config is updated before any backend
is instantiated.
"""
import os
import resource

# XLA's CPU compiler recurses deeply on the crypto modules' giant graphs;
# with the default 8 MB pthread stacks (inherited from RLIMIT_STACK at
# thread creation) it segfaults inside backend_compile — observed at
# fp12.pow_const, the G2 group law, and predict_homomorphic. Raise the
# limit BEFORE jax spawns its compile threads.
# NOTE: must be a large FINITE value — with RLIMIT_STACK=unlimited glibc
# falls back to the 8 MB default for new pthreads. Keep the existing hard
# limit (raising it needs privileges); cap the soft limit to it.
_STACK = 1 << 30  # 1 GiB
try:
    _soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
    _want = _STACK if _hard == resource.RLIM_INFINITY else min(_STACK, _hard)
    resource.setrlimit(resource.RLIMIT_STACK, (_want, _hard))
except (ValueError, OSError):
    pass

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Pin the CPU codegen ISA: persistent-cache AOT entries compiled with
# auto-detected machine features have been observed to SIGILL/segfault when
# reloaded in a process that detects a different feature set.
if "xla_cpu_max_isa" not in _flags:
    _flags = (_flags + " --xla_cpu_max_isa=AVX2").strip()
# Unoptimized CPU codegen: the crypto test modules are huge (256-step
# scans over pairing towers) and the optimizing CPU pipeline has segfaulted
# under the accumulated compile load of a full suite run (observed crashes
# inside backend_compile at fp12.pow_const / G2 group law). Tests check
# semantics, not CPU speed; opt level 0 compiles far faster and smaller.
if "xla_backend_optimization_level" not in _flags:
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

# On-disk persistent compilation cache for the test suite. An earlier
# jaxlib crashed deserializing large crypto executables
# (compilation_cache.get_executable_and_time on a pairing kernel), so this
# stayed off; re-validated on the current jaxlib with the ISA pinned to
# AVX2 above (the pin makes cache entries stable across feature
# detection), populate+reload of the heaviest compiled-GT-tier tests is
# clean and roughly halves their wall time. The suite's XLA compile bill
# is most of its 870 s tier-1 budget, so warm reruns need this to keep
# headroom as the suite grows. DRYNX_TEST_JAX_CACHE=0 disables;
# DRYNX_TEST_JAX_CACHE=<dir> relocates (default: .jax_cache_tests/ at the
# repo root, gitignored).
_cache = os.environ.get("DRYNX_TEST_JAX_CACHE", "")
if _cache != "0":
    if not _cache:
        _cache = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache_tests")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _cache)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# Of the kernel's per-process limit on memory mappings (vm.max_map_count,
# 65530 by default). One module has been seen to add 30k (test_encoding).
_MAPPINGS_BUDGET = 20000


def _n_mappings() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:     # no procfs to watch the limit with: always release
        return _MAPPINGS_BUDGET + 1


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop jax's in-memory program caches after a test module that leaves
    the process over its budget of memory mappings.

    Tier-1 is ONE process, and every compiled CPU program keeps mappings
    for its code: with all files collecting, the process reached the
    kernel's limit inside test_net_plane and the next compile segfaulted
    (PR 21: test_elgamal alone adds ~15k mappings, test_encoding ~30k).
    clear_caches() gives them back (41k -> 0.7k measured after
    test_encoding); programs that a later module shares come back from the
    persistent cache above."""
    yield
    if _n_mappings() > _MAPPINGS_BUDGET:
        jax.clear_caches()
        gc.collect()
