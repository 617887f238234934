"""Pre-warm the persistent XLA compilation cache for the TPU bench paths.

Runs every program the proofs-on benchmark needs — the fused exec phases,
batched range-proof creation (incl. the per-base GT window tables), joint
RLC verification, and the keyswitch proofs — once at bench shapes, so a
subsequent driver `bench.py` run pays Mosaic re-LOWERING only (jax has no
persistent lowering cache; the compile side hits `.jax_cache`).

Run AFTER any kernel change and BEFORE the driver bench:
    python scripts/prewarm.py            # TPU (default backend)
    python scripts/prewarm.py --cpu      # CPU shapes (rarely useful)
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    t0 = time.time()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()

    def log(msg):
        print(f"[{time.time() - t0:7.1f}s] {msg}", file=sys.stderr,
              flush=True)

    import numpy as np

    from drynx_tpu import flagship
    from drynx_tpu.models import logreg as lr
    from drynx_tpu.proofs import requests as rq
    from drynx_tpu.service.service import LocalCluster

    log(f"backend: {jax.default_backend()}")
    num_dps = 10
    X, y, params = flagship.pima_shaped_problem(
        num_dps=num_dps, n_records=768, d=8, max_iterations=450)
    cluster = LocalCluster(n_cns=3, n_dps=num_dps, n_vns=3, seed=4,
                           dlog_limit=10000)
    for i, dp in enumerate(cluster.dps.values()):
        Xi, yi = lr.shard_for_dp(X, y, i, num_dps)
        dp.data = (Xi, yi)
    V = params.num_coeffs()
    sq = cluster.generate_survey_query(
        "log_reg", proofs=1, lr_params=params, ranges=[(16, 5)] * V,
        thresholds=1.0)
    log("running one full proofs-on survey (populates every cache entry)")
    res = cluster.run_survey(sq)
    codes = set(res.block.data.bitmap.values())
    assert codes == {rq.BM_TRUE}, f"dirty bitmap: {codes}"
    assert np.all(np.isfinite(res.result))
    log("prewarm complete; timers: " + ", ".join(
        f"{k}={v:.2f}s" for k, v in res.timers.items()))


if __name__ == "__main__":
    main()
