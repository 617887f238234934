#!/usr/bin/env bash
# Fast pre-commit gate: static analyzer + the quick tier-1 tests.
# The whole of tier-1 is the line in ROADMAP.md ("Tier-1 verify").
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== changed-files lint (fast tier: impacted set = changed files +"
echo "== transitive importers; DRYNX_SKIP_JAX_INIT skips accelerator setup"
echo "== in the jax-free lint process, <2s for a leaf-file change) =="
DRYNX_SKIP_JAX_INIT=1 python -m drynx_tpu.analysis --changed-only

echo "== static analysis (python -m drynx_tpu.analysis, whole-program) =="
DRYNX_SKIP_JAX_INIT=1 python -m drynx_tpu.analysis drynx_tpu/ "$@"

echo "== sarif rendering smoke (codeFlows for CI annotation) =="
DRYNX_SKIP_JAX_INIT=1 python -m drynx_tpu.analysis tests/fixtures/lintpkg \
    --no-baseline --format sarif > /dev/null || test $? -eq 1

echo "== dataflow + sarif unit tests =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly tests/test_dataflow.py

echo "== concurrency tier (engine unit tests + fixture goldens; the"
echo "== DRYNX_LOCK_TRACE dynamic cross-check runs in the chaos tier) =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly -m 'not chaos' \
    tests/test_concurrency_analysis.py

echo "== determinism tier (taint-engine unit tests + fixture goldens +"
echo "== real-tree clean gate; the DRYNX_DET_TRACE two-run replay"
echo "== cross-check runs in the chaos tier) =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly -m 'not chaos' \
    tests/test_determinism_analysis.py

echo "== proto tier (typestate unit tests + fixture goldens + real-tree"
echo "== clean gate; the DRYNX_PROTO_TRACE runtime lifecycle conformance"
echo "== cross-check runs in the chaos tier) =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly -m 'not chaos' \
    tests/test_typestate_analysis.py

echo "== precompile registry smoke (trace+lower the proofs-on program set) =="
JAX_PLATFORMS=cpu python -m drynx_tpu.precompile --dry-run --quiet

echo "== quick tests =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly \
    tests/test_static_analysis.py \
    tests/test_analysis_rules.py \
    tests/test_precompile.py \
    tests/test_bench_supervisor.py \
    tests/test_field.py \
    tests/test_refimpl.py \
    tests/test_batching.py \
    tests/test_service_vn.py \
    tests/test_datasets_timedata.py

echo "== chaos quick tier (seeded fault injection, -m 'chaos and not slow';"
echo "== + the DRYNX_LOCK_TRACE dynamic/static lock-order cross-check"
echo "== + the DRYNX_DET_TRACE same-seed byte-identity replay check"
echo "== + the DRYNX_PROTO_TRACE lifecycle-automata conformance check) =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly \
    -m 'chaos and not slow' tests/test_resilience.py \
    tests/test_concurrency_analysis.py \
    tests/test_determinism_analysis.py \
    tests/test_typestate_analysis.py

echo "== pool smoke (store lifecycle: create->persist->reopen->consume->refill) =="
python scripts/pool_smoke.py > /dev/null

echo "== server tier (standing scheduler quick tests, the -m soak mini-soak"
echo "== among them, + 3-survey demo) =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:randomly -m 'not slow' \
    tests/test_server.py tests/test_loadgen.py
JAX_PLATFORMS=cpu python scripts/serve_surveys.py > /dev/null

echo "check.sh: all green"
