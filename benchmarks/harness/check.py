"""The comparison that decides `correct`.

Every survey the window completed is compared with the plain reference by
the reference module's `compare`; of each number the worst survey's reading
is kept and held to the limit the configuration's file gives it under
`limits`. Three numbers are the harness's own, each with the limit 0:
`dps_missing` (a data provider of the roster that did not answer; its limit
sits in the configuration's file with the others), `failed_surveys` (a
survey that raised) and `host_oracle_calls` (a crypto op that left the chip
for the host oracle, in set-up or window).
"""
from __future__ import annotations


def compare_window(config: dict, reference, expected: dict, records: list,
                   host_oracle_calls: int) -> dict:
    """{name: {"value": worst reading, "limit": its limit}}."""
    limits = config["limits"]
    worst: dict = {}
    for rec in records:
        if rec.outputs is None:
            continue
        numbers = dict(reference.compare(config, expected, rec.outputs))
        numbers["dps_missing"] = rec.outputs["dps_missing"]
        for name, value in numbers.items():
            worst[name] = max(worst.get(name, 0), value)
    compared = {}
    for name, limit in limits.items():
        # a number no survey produced has not been shown to hold
        compared[name] = {"value": worst.get(name, float("inf")),
                          "limit": limit}
    compared["failed_surveys"] = {
        "value": sum(1 for r in records if r.outputs is None), "limit": 0}
    compared["host_oracle_calls"] = {"value": host_oracle_calls, "limit": 0}
    return compared


def verdict(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
