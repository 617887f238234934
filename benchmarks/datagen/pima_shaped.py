"""Pima-shaped rows from the seed: n_dps * rows_per_dp records of n_features
real features with a 0/1 label, one pool row-sharded `i % n_dps` so that no
two data providers hold the same row (the paper's GetDataForDataProvider).

The benchmark's own copy of the shape of `drynx_tpu/models/logreg.py`
`synthetic_dataset` and `shard_for_dp` (PERF.md, Open questions): features
of differing scale and offset, labels from a hidden logistic model. The
standardisation the query carries (`means`, `std_devs`) is the pool's.
"""
import numpy as np


def generate(config: dict, seed: int) -> dict:
    n_dps = config["roster"]["n_dps"]
    d = int(config["n_features"])
    n = n_dps * int(config["rows_per_dp"])
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)) * rng.uniform(0.5, 3.0, size=d) \
        + rng.uniform(-2, 2, size=d)
    w_true = rng.normal(size=d + 1)
    z = w_true[0] + ((X - X.mean(0)) / X.std(0)) @ w_true[1:]
    y = (1 / (1 + np.exp(-z)) > rng.uniform(size=n)).astype(np.int64)
    rows = np.arange(n)
    return {"per_dp": [(X[rows % n_dps == i], y[rows % n_dps == i])
                       for i in range(n_dps)],
            "means": tuple(float(v) for v in X.mean(0)),
            "std_devs": tuple(float(v) for v in X.std(0)),
            "n_records": n}
