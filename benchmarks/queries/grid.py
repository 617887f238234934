"""Query of an op over a bucket grid (max, min, frequency count ...): the
grid's two ends. A data provider sends one encrypted value a bucket."""


def query_kwargs(config: dict, data: dict) -> dict:
    lo = int(config["query_min"])
    return {"query_min": lo, "query_max": lo + int(config["n_buckets"]) - 1}


def n_values(config: dict) -> int:
    return int(config["n_buckets"])
