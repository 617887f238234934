"""Flagship pipeline: fully-jitted encrypted logistic-regression survey.

This is the TPU equivalent of the reference's north-star workload
(SURVEY.md §3.4; reference services/service_test.go:1082-1130 — Pima, 10 DPs,
K=2, precision 1e0, GD step 0.1): every DP encodes + encrypts its local
approximation tensors, ciphertexts are homomorphically aggregated, the
collective key-switches the aggregate to the querier, the querier decrypts
(discrete-log table) and runs gradient descent — all as ONE jitted program.

The same program builds two ways:
  * single-chip (`build_pipeline`): server/DP loops become batched axes and
    tree reductions on one device — used by bench.py and __graft_entry__.entry.
  * multi-chip (`build_sharded_pipeline`): DPs/servers ride a mesh axis with
    butterfly all-reduces (drynx_tpu.parallel), the ciphertext vector is
    sharded over a second mesh axis — used by __graft_entry__.dryrun_multichip.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .crypto import curve as C
from .crypto import elgamal as eg
from .crypto import field as F
from .models import logreg as lr
from .parallel import collective as col


@dataclasses.dataclass
class SurveySetup:
    """Keys + tables for one survey: n_servers CNs, a querier, 10 DPs."""

    server_secrets: np.ndarray    # (n_servers, 16) scalar limbs
    coll_pub_table: jnp.ndarray   # (64, 16, 3, 16) fixed-base table
    query_secret: int
    query_pub_table: jnp.ndarray
    dlog: eg.DecryptionTable

    @classmethod
    def create(cls, n_servers: int = 3, dlog_limit: int = 10000, seed: int = 4):
        rng = np.random.default_rng(seed)
        secrets, pubs = zip(*[eg.keygen(rng) for _ in range(n_servers)])
        coll = col.collective_key(pubs)
        qx, qpub = eg.keygen(rng)
        return cls(
            server_secrets=np.stack([eg.secret_to_limbs(x) for x in secrets]),
            coll_pub_table=eg.pub_table(coll).table,
            query_secret=qx,
            query_pub_table=eg.pub_table(qpub).table,
            dlog=eg.DecryptionTable(limit=dlog_limit),
        )


def build_pipeline(setup: SurveySetup, params: lr.LRParams):
    """Single-chip jitted survey step.

    Returns fn(dp_stats, enc_rs, ks_rs) -> (weights, dec_ints, found):
      dp_stats: int64 (n_dps, V) local fixed-point stat vectors
      enc_rs:   uint32 (n_dps, V, 16) encryption blinding scalars
      ks_rs:    uint32 (n_servers, V, 16) key-switch randomness
    """
    base_tbl = eg.BASE_TABLE.table
    coll_tbl = setup.coll_pub_table
    q_tbl = setup.query_pub_table
    srv_x = jnp.asarray(setup.server_secrets)
    qx = jnp.asarray(eg.secret_to_limbs(setup.query_secret))
    dl = setup.dlog
    keys, xs, ysign, vals = dl.keys, dl.xs, dl.ysign, dl.vals

    def fn(dp_stats, enc_rs, ks_rs):
        # DP-side: encrypt every stat of every DP (one big batch; int64
        # plaintexts ride the truncated small-scalar ladder).
        cts = eg.encrypt_ints_with_tables(base_tbl, coll_tbl, dp_stats,
                                          enc_rs)
        # Collective aggregation (CN tree -> on-chip tree reduce).
        agg = C.sum_points(cts)
        # Key switch: per-server contributions (broadcast batch — one big
        # flat batch feeds the Pallas ladder kernel), then reduce.
        kc, cc = col.keyswitch_contribution(
            agg[None], srv_x[:, None, :], ks_rs, q_tbl)
        switched = col.keyswitch_finish(
            agg, C.sum_points(kc), C.sum_points(cc))
        # Querier decrypt + discrete log.
        pts = eg.decrypt_point(switched, qx)
        dec, found = eg._table_lookup(keys, xs, ysign, vals, pts)
        # Gradient descent on the approximated cost.
        Ts = lr.unpack(dec, params)
        w = lr.train(Ts, params)
        return w, dec, found

    return fn


def make_inputs(X, y, params: lr.LRParams, num_dps: int = 10, seed: int = 0):
    """Host-side: per-DP stats + randomness for the pipeline."""
    stats = np.stack([
        np.asarray(lr.encode_clear(*lr.shard_for_dp(X, y, i, num_dps), params))
        for i in range(num_dps)
    ])
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    V = stats.shape[1]
    enc_rs = eg.random_scalars(k1, (num_dps, V))
    return jnp.asarray(stats), enc_rs, k1, k2


def pima_shaped_problem(num_dps: int = 10, n_records: int = 768, d: int = 8,
                        max_iterations: int = 450):
    """Pima-benchmark-shaped problem (reference TIFS/logRegV2.py setting:
    768 records x 10 DPs, 8 features, K=2, 450 iterations).

    Every DP gets DISTINCT rows: one pool of num_dps*n_records records is
    row-sharded i % num_dps (reference GetDataForDataProvider,
    logistic_regression.go:1427-1443) — n_records rows PER DP, i.e. 10x the
    reference's per-DP load, and no two DPs hold the same data."""
    X, y = lr.synthetic_dataset(n=n_records * num_dps, d=d, seed=13)
    p = lr.LRParams(
        k=2, precision=1.0, lambda_=1.0, step=0.1,
        max_iterations=max_iterations, n_features=d,
        n_records=len(y), dtype="float32",
        means=tuple(np.mean(X, 0)), std_devs=tuple(np.std(X, 0)))
    return X, y, p


__all__ = ["SurveySetup", "build_pipeline", "make_inputs",
           "pima_shaped_problem"]
