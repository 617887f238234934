"""Plain reference for `max` over a bucket grid, independent of the program.

Semantics (Drynx, encoding of min/max): every data provider reports, for
each bucket g of the grid, the bit (its largest value > g); the servers add
the bits, so the decrypted vector holds at g the number of providers whose
largest value lies above g, and the answer is the first bucket at which
that number is zero: the largest value anyone holds.
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
    broken: the provider that holds the largest value did not answer."""
    if kind != "drop_max_dp":
        raise ValueError(f"max has no control {kind!r}")
    keep = [v for i, v in enumerate(data["per_dp"])
            if i != int(np.argmax(expected["local_max"]))]
    broken = expect(config, {"per_dp": keep})
    return {"values": broken["decrypted"],
            "found": np.ones(broken["decrypted"].shape, dtype=bool),
            "result": broken["answer"]}
