"""Plain reference for a differentially private `sum`, independent of the
program.

Semantics (Drynx, SURVEY.md section 2.2; reference services/service.go:
600-665): every data provider reports the sum of its rows, the servers add
the reports, and before the key switch one ciphertext of a shuffled,
re-randomised list of encrypted noise values is added to the aggregate. The
list is PUBLIC and deterministic (unlynx GenerateNoiseValuesScale): the
values mean, mean +- quanta, mean +- 2 quanta, ... each repeated in
proportion to the Laplace density exp(-|v - mean| / b), until `size` values
exist; what is secret is which of them the shuffle put first. So the
decrypted answer is the clear sum plus SOME member of the list, and the
decoded result is that decrypted value.
"""
import math

import numpy as np


def noise_list(size: int, mean: float, b: float, quanta: float,
               scale: float, limit: float) -> np.ndarray:
    """The published noise list, by the plain loop: candidate values in the
    order mean, mean + q, mean - q, mean + 2q, ...; a value beyond `limit`
    (if nonzero) is skipped; each is repeated round(density * size * q /
    2b) times, at least once; the list is cut at `size`, multiplied by
    `scale` and rounded to whole numbers."""
    values: list = []
    k = 0
    while len(values) < size and k <= 10 * size:
        for v in ([mean] if k == 0 else [mean + k * quanta,
                                         mean - k * quanta]):
            if limit and abs(v) > limit:
                continue
            density = math.exp(-abs(v - mean) / b)
            copies = max(1, int(round(density * size * quanta / (2.0 * b))))
            values.extend([v] * copies)
            if len(values) >= size:
                break
        k += 1
    return np.round(np.asarray(values[:size], dtype=np.float64)
                    * scale).astype(np.int64)


def expect(config: dict, data: dict) -> dict:
    d = config["diffp"]
    noise = noise_list(int(d["noise_list_size"]), float(d["lap_mean"]),
                       float(d["lap_scale"]), float(d["quanta"]),
                       float(d["scale"]), float(d["limit"]))
    clear = sum(int(np.sum(np.asarray(rows, dtype=np.int64)))
                for rows in data["per_dp"])
    return {"clear_sum": clear, "noise": noise,
            "members": np.unique(noise)}


def compare(config: dict, expected: dict, out: dict) -> dict:
    """The numbers of one survey, each held to the limit of the same name
    in the configuration's file."""
    values = np.asarray(out["values"], dtype=np.int64).reshape(-1)
    found = np.asarray(out["found"], dtype=bool).reshape(-1)
    if values.shape != (1,):
        return {"dlog_missed": 1, "noise_outside_list": 1,
                "answer_diff": float("inf")}
    drawn = int(values[0]) - expected["clear_sum"]
    answer = out["result"]
    return {
        "dlog_missed": int((~found).sum()),
        "noise_outside_list": int(drawn not in set(
            expected["members"].tolist())),
        "answer_diff": (float("inf") if answer is None
                        else abs(int(answer) - int(values[0]))),
    }


def control(config: dict, data: dict, expected: dict, kind: str) -> dict:
    """The reference in the program's place with one stated guarantee
    broken: the noise that was added is one quantum beyond the largest
    value of the published list."""
    if kind != "noise_off_list":
        raise ValueError(f"sum_diffp has no control {kind!r}")
    d = config["diffp"]
    off = int(expected["members"].max()) + max(
        1, int(round(float(d["quanta"]) * float(d["scale"]))))
    answer = expected["clear_sum"] + off
    return {"values": np.asarray([answer], dtype=np.int64),
            "found": np.ones((1,), dtype=bool), "result": answer}
