"""One or more whole numbers per data provider, drawn uniformly from the
configuration's grid [query_min, query_min + n_buckets) by the seed."""
import numpy as np


def generate(config: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n_dps = config["roster"]["n_dps"]
    lo = int(config["query_min"])
    hi = lo + int(config["n_buckets"])            # exclusive
    values = rng.integers(lo, hi, size=(n_dps, int(config["values_per_dp"])),
                          dtype=np.int64)
    return {"per_dp": [values[i] for i in range(n_dps)]}
