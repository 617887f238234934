"""The system under test, as the benchmark holds it: an in-process
`LocalCluster` with the configuration's roster, its data providers loaded
with the generator's rows, and a maker of fresh survey queries.

This and `queries/` are the only modules of the benchmark that import the
program. What this one takes from it: `LocalCluster.run_survey` (the entry the window drives), the
result's `timers` (PhaseTimers spans), `HOST_ORACLE_CALLS`,
`pallas_ops.available()` / `INTERPRET`, and the one compile-cache rule.
"""
from __future__ import annotations

import numpy as np


def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def chip_refusal(device: dict, chips: int):
    """Why this machine cannot run the cell, or None."""
    from drynx_tpu.crypto import pallas_ops as po

    if device["platform"] != "tpu":
        return f"no TPU: jax.devices() is {device['platform']}"
    if device["count"] < chips:
        return f"the cell asks for {chips} chips, jax sees {device['count']}"
    if po.INTERPRET or not po.available():
        return "the Pallas kernels would not run as Mosaic code here"
    return None


def enable_cache() -> str:
    """The program's one compile-cache rule (JAX_COMPILATION_CACHE_DIR,
    else <checkout>/.jax_cache), with every program kept whatever its
    compile time: jax's default keeps only compiles over a second, and a
    survey process makes some seventy smaller ones (PERF.md, PR 21)."""
    import jax

    from drynx_tpu.utils.cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def host_oracle_calls() -> int:
    from drynx_tpu.crypto import batching as B

    return int(sum(B.HOST_ORACLE_CALLS.values()))


def memory_stats() -> dict:
    """The byte counters of the fullest device's allocator, each with its
    peak: arrays held (`in_use`), running programs' scratch (`reserved`)."""
    import jax

    per_device = [{k: int(v) for k, v in (d.memory_stats() or {}).items()
                   if "bytes" in k} for d in jax.local_devices()]
    return max(per_device, key=memory_peak)


class System:
    """One cluster, loaded, with `new_query()` and `run(query, seed)`.
    `query_kwargs` is what the configuration's `queries/<name>.py` makes of
    it: the op's own arguments to `generate_survey_query`."""

    def __init__(self, config: dict, data: dict, seed: int,
                 query_kwargs: dict):
        from drynx_tpu.service.service import LocalCluster

        roster = config["roster"]
        # precompile="off": no registry warm-up on this path (PERF.md); the
        # benchmark's one warm-up survey compiles what the cell uses
        self.cluster = LocalCluster(
            n_cns=roster["n_cns"], n_dps=roster["n_dps"],
            n_vns=roster["n_vns"], seed=seed % (2 ** 32),
            dlog_limit=int(config["dlog_limit"]), precompile="off")
        self.roster = sorted(self.cluster.dps)
        for dp, rows in zip(self.cluster.dps.values(), data["per_dp"]):
            dp.data = rows
        self._op = config["op"]
        self._kwargs = dict(query_kwargs, proofs=int(config["proofs"]))

    def new_query(self):
        return self.cluster.generate_survey_query(self._op, **self._kwargs)

    def run(self, query, seed: int):
        """The timed call. Returns the program's SurveyResult."""
        return self.cluster.run_survey(query, seed=seed)


def outputs_of(result, roster: list) -> dict:
    """What the comparison reads of one survey, copied to plain numpy."""
    answer = result.result
    if answer is not None and not isinstance(answer, (int, dict, list)):
        answer = np.asarray(answer)
    return {"values": np.asarray(result.decrypted.values),
            "found": np.asarray(result.decrypted.found),
            "result": answer,
            "dps_missing": len(set(roster) - set(result.responders))}


def phase_seconds(result) -> dict:
    out: dict = {}
    for name, t0, t1 in phase_spans(result):
        out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def phase_spans(result) -> list:
    """(name, t0, t1) on time.perf_counter's clock."""
    return result.timers.spans()


def memory_peak(stats: dict) -> int:
    """The most bytes the device held at one time, from below. The TPU's
    allocator counts two things apart: the arrays the process holds
    (`peak_bytes_in_use`) and the scratch of the programs it has loaded
    (`peak_bytes_reserved`: reserved when a program first runs, the largest
    program's `memory_analysis()` temp size to within 5 %, kept while the
    program is loaded; PERF.md, PR 25, refusal round). The device held both
    at once, but the two peaks need not fall together, so the larger of
    them is what it surely held; their sum it may not have."""
    return max(stats.get("peak_bytes_in_use", 0),
               stats.get("peak_bytes_reserved", 0))
