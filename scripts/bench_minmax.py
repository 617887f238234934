"""Timed large-range max survey (VERDICT round-3 missing #3; reference
maxOpti.py measures ranges 1k -> 1M at near-flat optimized cost).

Runs the max operation with proofs ON over a [0, R) bucket range: the
encoding is R bucket-bits per DP (reference encoding/min_max.go:87-123),
each carrying a (2, 1) bit range proof; creation and the joint VN
verification run as single device batches, so cost scales with R only
through batch size — the TPU analogue of the reference's "optimized" bars.

Usage: python scripts/bench_minmax.py [--range 10000] [--dps 5] [--cpu]
Prints one JSON line per run.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--range", type=int, default=10_000, dest="rng",
                    help="bucket range R (query_max = R - 1)")
    ap.add_argument("--dps", type=int, default=5)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()

    import numpy as np

    from drynx_tpu.proofs import requests as rq
    from drynx_tpu.service.service import LocalCluster

    R = args.rng
    cluster = LocalCluster(n_cns=3, n_dps=args.dps, n_vns=3, seed=9,
                           dlog_limit=max(args.dps + 2, 100))
    rng = np.random.default_rng(5)
    expected_max = 0
    for dp in cluster.dps.values():
        dp.data = rng.integers(0, R, size=(64,)).astype(np.int64)
        expected_max = max(expected_max, int(dp.data.max()))

    sq = cluster.generate_survey_query(
        "max", query_min=0, query_max=R - 1, proofs=1,
        ranges=[(2, 1)] * R, thresholds=1.0)

    t0 = time.perf_counter()
    res = cluster.run_survey(sq)
    dt = time.perf_counter() - t0
    codes = set(res.block.data.bitmap.values())
    assert codes == {rq.BM_TRUE}, f"dirty bitmap: {codes}"
    assert int(res.result) == expected_max, (res.result, expected_max)
    print(json.dumps({
        "metric": "max_survey_proofs_on_seconds", "range": R,
        "n_dps": args.dps, "value": round(dt, 3), "unit": "s",
        "result_ok": True,
        "timers": {k: round(v, 3) for k, v in res.timers.items()},
    }), flush=True)


if __name__ == "__main__":
    main()
