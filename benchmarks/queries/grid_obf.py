"""Query of a zero / non-zero op over a bucket grid (max, min, union,
intersection) run with obfuscation: the grid's two ends and the flag. A
data provider sends one encrypted value a bucket; every computing node then
multiplies the whole aggregate by secret scalars of its own."""


def query_kwargs(config: dict, data: dict) -> dict:
    lo = int(config["query_min"])
    return {"query_min": lo, "query_max": lo + int(config["n_buckets"]) - 1,
            "obfuscation": bool(config["obfuscation"])}


def n_values(config: dict) -> int:
    return int(config["n_buckets"])
