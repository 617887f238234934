"""Plain reference for `max` over a bucket grid run WITH OBFUSCATION,
independent of the program.

Semantics (Drynx, encoding of min/max, then the obfuscation protocol):
every data provider reports, for each bucket g of the grid, the bit (its
largest value > g); the servers add the bits, so the aggregate holds at g
the number of providers whose largest value lies above g. Every computing
node then multiplies every ciphertext by a fresh secret scalar of its own,
so what the querier decrypts at g is (s_1 s_2 s_3 count_g) B: the identity
where the count is zero, and elsewhere a point that says nothing of the
count. The answer is the first bucket that decrypts to zero: the largest
value anyone holds.

So of the decrypted vector only the zero pattern can be compared, and a
non-zero bucket MUST NOT resolve: a count times three 254-bit scalars lies
in a discrete-log table of 10 000 entries with probability 1e-73, so one
resolved non-zero bucket means a count went out not obfuscated. A bucket
the table misses is no fault here; it is what a non-zero bucket has to
give.
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
    counts = expected["decrypted"]
    if values.shape != counts.shape:
        return {"zero_pattern_diff": int(counts.size),
                "nonzero_resolved": int(counts.size),
                "answer_diff": float("inf")}
    answer = out["result"]
    decrypted_to_zero = found & (values == 0)
    return {
        "zero_pattern_diff": int((decrypted_to_zero != (counts == 0)).sum()),
        "nonzero_resolved": int((found & (counts != 0)).sum()),
        "answer_diff": (float("inf") if answer is None
                        else abs(int(answer) - expected["answer"])),
    }


def control(config: dict, data: dict, expected: dict, kind: str) -> dict:
    """The reference in the program's place with one stated guarantee
    broken: no node obfuscated, so the clear counts come out, all found."""
    if kind != "obfuscation_off":
        raise ValueError(f"max with obfuscation has no control {kind!r}")
    counts = expected["decrypted"]
    return {"values": counts.copy(),
            "found": np.ones(counts.shape, dtype=bool),
            "result": expected["answer"]}
