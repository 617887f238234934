"""Plain reference for `max` over a bucket grid on a roster of many
computing nodes, independent of the program.

Semantics (Drynx, encoding of min/max, then the key switch): every data
provider reports, for each bucket g of the grid, the bit (its largest value
> g); the servers add the bits, so the aggregate holds at g the number of
providers whose largest value lies above g, encrypted under the collective
key, the sum of ALL the computing nodes' keys. Every computing node then
contributes r_i B and r_i Q - x_i K with its own secret x_i; the sum of all
the contributions takes the collective key off and puts the querier's on.
The answer is the first bucket at which the count is zero: the largest value
anyone holds. Neither depends on how many computing nodes the roster has;
what depends on it is that EVERY one of them has to contribute.
"""
import numpy as np


def expect(config: dict, data: dict) -> dict:
    lo = int(config["query_min"])
    grid = np.arange(lo, lo + int(config["n_buckets"]), dtype=np.int64)
    local_max = np.asarray([int(np.max(v)) for v in data["per_dp"]],
                           dtype=np.int64)
    counts = np.zeros(grid.shape, dtype=np.int64)
    for m in local_max:
        counts += (grid < m)
    return {"decrypted": counts, "answer": int(local_max.max()),
            "local_max": local_max}


def compare(config: dict, expected: dict, out: dict) -> dict:
    """The numbers of one survey, each held to the limit of the same name
    in the configuration's file."""
    values = np.asarray(out["values"], dtype=np.int64)
    found = np.asarray(out["found"], dtype=bool)
    want = expected["decrypted"]
    if values.shape != want.shape:
        return {"decrypted_diff_max": float("inf"),
                "dlog_missed": int(want.size), "answer_diff": float("inf")}
    answer = out["result"]
    return {
        "decrypted_diff_max": int(np.abs(np.where(found, values, want)
                                         - want).max()),
        "dlog_missed": int((~found).sum()),
        "answer_diff": (float("inf") if answer is None
                        else abs(int(answer) - expected["answer"])),
    }


def control(config: dict, data: dict, expected: dict, kind: str) -> dict:
    """The reference in the program's place with one stated guarantee
    broken: one computing node's contribution is missing. Every switched
    ciphertext then still carries that node's x_i K, a point no table
    holds, so the querier's decryption resolves no bucket: `found` all
    false, whatever the counts were."""
    if kind != "drop_one_cn":
        raise ValueError(f"max over many computing nodes has no control "
                         f"{kind!r}")
    counts = expected["decrypted"]
    return {"values": np.zeros_like(counts),
            "found": np.zeros(counts.shape, dtype=bool),
            "result": expected["answer"]}
