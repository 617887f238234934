"""Every data file of the benchmark loads and resolves by name."""
import glob
import json
import os

import pytest

from benchmarks.harness import cells
from conftest import ROOT, WAITING

BENCH = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
HERE = os.path.join(ROOT, "benchmarks")


def _names(kind):
    return sorted(os.path.basename(p)[:-len(".json")]
                  for p in glob.glob(os.path.join(HERE, kind, "*.json")))


@pytest.mark.parametrize("kind", ["configs", "traffic", "metrics"])
def test_every_file_is_json_and_is_used(kind):
    used = {"configs": {c["name"] for c in
                        BENCH["configs"] + WAITING["configs"]},
            "traffic": {w["traffic"] for w in BENCH["workloads"]},
            "metrics": {m["name"] for m in
                        BENCH["end_to_end"] + BENCH["per_layer"]}}[kind]
    assert set(_names(kind)) == used


@pytest.mark.parametrize("cell_name",
                         [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell_name):
    cell = cells.load_cell(ROOT, cell_name)
    assert cell.chips == 1
    assert cell.config["name"] == cell_name.split(".")[0]
    for kind, name in (("datagen", cell.config["datagen"]),
                       ("queries", cell.config["query"]),
                       ("reference", cell.config["reference"])):
        assert cells.plugin(ROOT, kind, name)
    assert cell.config["traced_surveys"] >= 1
    assert {"setup_s", "survey_s.mean"} <= {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end + cell.per_layer:
        assert hasattr(cells.plugin(ROOT, "readers", m["spec"]["reader"]),
                       "read")
        assert m["spec"]["unit"] == m["unit"]
        if "moves" in m:
            assert m["spec"]["moves"] == m["moves"]
            assert m["spec"]["layer"] == m["layer"]


def test_configs_state_what_the_manifest_says():
    for entry in BENCH["configs"] + WAITING["configs"]:
        config = cells.load_json(os.path.join(ROOT, entry["file"]))
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert config["guarantees"]["every_dp_answers"] is True
        assert config["guarantees"]["host_oracle_calls"] == 0
        assert config["proofs"] == 0
        # exact answers are held exactly: no limit of these is ever loosened
        assert config["limits"]["decrypted_diff_max"] == 0
        assert config["limits"]["dlog_missed"] == 0
    grid = cells.load_json(os.path.join(
        HERE, "configs", "max-grid-10dp-exec.json"))
    assert grid["n_buckets"] % 4096 == 0


def test_manifest_is_within_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for group in ("configs", "workloads"):
        for e in BENCH[group]:
            assert all(len(e[k]) <= 200 for k in ("why", "source") if k in e)
    assert len(json.dumps(BENCH)) < 64 * 1024
