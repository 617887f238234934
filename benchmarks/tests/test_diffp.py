"""The differentially private sum (`diffp-sum-10dp-exec`): its data files,
the plain reference by a hand-worked case, the control, the whole-list
check of `check_dro.py`, and the cell end to end through `run_cell`, all at
a small size of this file's own (`conftest.SMALL` has no entry for it)."""
import json
import os

import numpy as np
import pytest

from benchmarks import check_dro
from benchmarks.check_seeds import merge
from benchmarks.harness import cells, check, runner, window, work_dro
from conftest import ROOT
from test_rehearsal import _FakeTracer, _run, _sut

CELL = "diffp-sum-10dp-exec.one-querier"
NAME = "diffp-sum-10dp-exec"
# a list of 64 in slabs of 16 over 4 providers of 6 rows: the CPU path takes
# seconds per hundred ciphertexts
SMALL = {"roster": {"n_dps": 4}, "values_per_dp": 6, "dlog_limit": 600,
         "diffp": {"noise_list_size": 64, "lap_scale": 2.0, "limit": 8.0}}
NEW_METRICS = {"phase_s.dro", "step_s.dro.noise_enc", "step_s.dro.zero_enc",
               "step_s.dro.permute_add", "device_glue_s.dro",
               "dro.encryptions_per_survey", "kernel.dro_hbm_roofline"}


@pytest.fixture
def small_copy(bench_copy, monkeypatch):
    from drynx_tpu.parallel import dro

    monkeypatch.setattr(dro, "CHUNK", 16)
    path = os.path.join(bench_copy, "benchmarks", "configs", NAME + ".json")
    config = merge(cells.load_json(path), SMALL)
    with open(path, "w") as f:
        json.dump(config, f)
    return bench_copy


def test_the_new_files_load_and_say_what_the_issue_says():
    cell = cells.load_cell(ROOT, CELL)
    config = cell.config
    assert cell.chips == 1 and config["name"] == NAME
    assert config["op"] == "sum" and config["proofs"] == 0
    assert config["roster"] == {"n_cns": 3, "n_dps": 10, "n_vns": 3}
    assert config["diffp"] == {
        "noise_list_size": 262144, "lap_mean": 0.0, "lap_scale": 20.0,
        "quanta": 1.0, "scale": 1.0, "limit": 400.0}
    assert config["diffp"]["noise_list_size"] == 64 * 4096
    assert sorted(config["reduced"]) == ["noise_list_size"]
    assert config["reduced"]["noise_list_size"]["source"] == 1000000
    assert set(config["assumed"]) >= {"lap_mean", "lap_scale", "quanta",
                                      "scale", "limit", "values_per_dp",
                                      "value_range"}
    assert config["guarantees"] == {
        "every_dp_answers": True, "noise_from_published_list": True,
        "every_cn_shuffles": 3, "fresh_rerandomisation_every_pass": True,
        "host_oracle_calls": 0}
    assert config["limits"] == {"dlog_missed": 0, "noise_outside_list": 0,
                                "answer_diff": 0, "dps_missing": 0}
    assert config["control"] == {"reference": "noise_off_list"}
    assert cell.traffic["warmup_surveys"] == 1
    for kind in ("datagen", "query", "reference"):
        assert cells.plugin(ROOT, {"query": "queries"}.get(kind, kind),
                            config[kind])
    per_layer = {m["name"]: m for m in cell.per_layer}
    assert NEW_METRICS <= set(per_layer)
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "survey_s.mean"
    assert not {n for n in per_layer if ".fused_" in n}
    grid = {m["name"] for m in cells.load_cell(
        ROOT, "max-grid-10dp-exec.one-querier").per_layer}
    assert not grid & NEW_METRICS
    assert {n for n in grid if ".fused_" in n} == {
        "setup.trace_s.fused_ks", "setup.lower_s.fused_ks",
        "setup.trace_s.fused_dec", "setup.lower_s.fused_dec"}


def test_the_data_is_the_configurations(bench_copy):
    config = cells.load_cell(ROOT, CELL).config
    data = cells.plugin(ROOT, "datagen", config["datagen"]).generate(
        config, 2 ** 31 + 5)
    assert len(data["per_dp"]) == 10
    rows = np.concatenate(data["per_dp"])
    assert rows.shape == (600,) and rows.min() >= 0 and rows.max() <= 15
    assert rows.sum() + 170 < config["dlog_limit"]
    kwargs = cells.plugin(ROOT, "queries", config["query"]).query_kwargs(
        config, data)
    assert (kwargs["query_min"], kwargs["query_max"]) == (0, 15)
    assert kwargs["diffp"].enabled()
    assert kwargs["diffp"].noise_list_size == 262144


def _reference():
    return cells.plugin(ROOT, "reference", "sum_diffp")


def test_the_noise_list_by_hand():
    """size 10, mean 0, b 1, quanta 1: value 0 is repeated
    round(1 * 10 / 2) = 5 times, +1 and -1 round(e^-1 * 5) = 2 times each,
    +2 max(1, round(e^-2 * 5) = 1): ten values."""
    ref = _reference()
    got = ref.noise_list(10, 0.0, 1.0, 1.0, 1.0, 0.0)
    assert got.tolist() == [0, 0, 0, 0, 0, 1, 1, -1, -1, 2]
    # the scale multiplies, the limit leaves values out
    assert ref.noise_list(10, 0.0, 1.0, 1.0, 3.0, 0.0).tolist() \
        == [0, 0, 0, 0, 0, 3, 3, -3, -3, 6]
    assert set(ref.noise_list(8, 0.0, 1.0, 1.0, 1.0, 1.0).tolist()) \
        == {-1, 0, 1}
    full = ref.noise_list(262144, 0.0, 20.0, 1.0, 1.0, 400.0)
    assert len(np.unique(full)) == 341
    assert (full.min(), full.max()) == (-170, 170)
    assert round(float((full == 0).mean()), 3) == 0.025


HAND = {"diffp": {"noise_list_size": 10, "lap_mean": 0.0, "lap_scale": 1.0,
                  "quanta": 1.0, "scale": 1.0, "limit": 0.0},
        "limits": {"dlog_missed": 0, "noise_outside_list": 0,
                   "answer_diff": 0, "dps_missing": 0}}
HAND_DATA = {"per_dp": [np.asarray([1, 2, 3]), np.asarray([10, 0, 5])]}


@pytest.mark.parametrize("values,found,result,want", [
    ([21], [True], 21, {"dlog_missed": 0, "noise_outside_list": 0,
                        "answer_diff": 0}),           # noise 0
    ([23], [True], 23, {"dlog_missed": 0, "noise_outside_list": 0,
                        "answer_diff": 0}),           # noise +2, a member
    ([20], [True], 20, {"dlog_missed": 0, "noise_outside_list": 0,
                        "answer_diff": 0}),           # noise -1
    ([19], [True], 19, {"dlog_missed": 0, "noise_outside_list": 1,
                        "answer_diff": 0}),           # -2 is no member
    ([24], [True], 24, {"dlog_missed": 0, "noise_outside_list": 1,
                        "answer_diff": 0}),
    ([21], [True], 22, {"dlog_missed": 0, "noise_outside_list": 0,
                        "answer_diff": 1}),           # decoded != decrypted
    ([21], [False], 21, {"dlog_missed": 1, "noise_outside_list": 0,
                         "answer_diff": 0}),
    ([21], [True], None, {"dlog_missed": 0, "noise_outside_list": 0,
                          "answer_diff": float("inf")}),
    ([21, 21], [True, True], 21, {"dlog_missed": 1, "noise_outside_list": 1,
                                  "answer_diff": float("inf")}),
])
def test_compare_by_hand(values, found, result, want):
    ref = _reference()
    expected = ref.expect(HAND, HAND_DATA)
    assert expected["clear_sum"] == 21
    assert expected["members"].tolist() == [-1, 0, 1, 2]
    out = {"values": np.asarray(values), "found": np.asarray(found),
           "result": result}
    assert ref.compare(HAND, expected, out) == want


def test_the_control_comes_out_not_correct():
    ref = _reference()
    for config, data in ((HAND, HAND_DATA),
                         (cells.load_cell(ROOT, CELL).config, None)):
        if data is None:
            data = cells.plugin(ROOT, "datagen", config["datagen"]).generate(
                config, 12345)
        expected = ref.expect(config, data)
        good = {"values": np.asarray([expected["clear_sum"]
                                      + int(expected["noise"][-1])]),
                "found": np.ones(1, bool), "dps_missing": 0}
        good["result"] = int(good["values"][0])
        fake = dict(ref.control(config, data, expected, "noise_off_list"),
                    dps_missing=0)
        assert int(fake["values"][0]) - expected["clear_sum"] \
            == int(expected["members"].max()) + 1
        verdicts = []
        for outputs in (good, fake):
            rec = window.SurveyRecord(0, 1, 0.0, 0.0, outputs, {}, [])
            compared = check.compare_window(config, ref, expected, [rec], 0)
            verdicts.append((check.verdict(compared), {
                k: c["value"] for k, c in compared.items()
                if c["value"] > c["limit"]}))
        assert verdicts[0] == (True, {})
        # by one of the cell's limits, not by each
        assert verdicts[1] == (False, {"noise_outside_list": 1})
    with pytest.raises(ValueError):
        ref.control(HAND, HAND_DATA, ref.expect(HAND, HAND_DATA), "other")


def test_the_phases_bytes_are_counted_from_its_sizes():
    config = cells.load_cell(ROOT, CELL).config
    assert work_dro.dro_bytes_per_survey(config) \
        == 262144 * (8 + 64 + 384) + 3 * 262144 * (384 + 64 + 384)
    assert work_dro.dro_bytes_per_survey(merge(config, SMALL)) \
        == 64 * 456 + 3 * 64 * 832


def test_two_surveys_end_to_end(small_copy, capsys):
    rc, line, earlier, err = _run(small_copy, CELL, capsys,
                                  seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is True, line["compared"]
    assert line["attempted"] == 1 and line["failed"] == 0
    assert set(line["compared"]) == {
        "dlog_missed", "noise_outside_list", "answer_diff", "dps_missing",
        "failed_surveys", "host_oracle_calls"}
    assert set(line["metrics"]) == {"survey_s.mean", "setup_s"}
    window_line = next(e for e in earlier if e["phase"] == "window")
    assert {"DROPhase", "DROPhase/noise_values", "DROPhase/noise_enc",
            "DROPhase/zero_enc", "DROPhase/permute_add",
            "DROPhase/pick_add"} <= set(window_line["phase_s_mean"])
    reference = next(e for e in earlier if e["phase"] == "reference")
    assert reference["surveys_compared"] == 2


def test_traced_run_reports_the_new_per_layer_metrics(small_copy, capsys,
                                                      monkeypatch):
    class WholeSurveyBusy(_FakeTracer):
        """One kernel op over the whole of each survey, so that some of it
        lies inside the noise phase."""

        def load(self):
            ops = [["_fixed_base_mul_flat.1 tpu_custom_call", s, d]
                   for _, s, d in self._marks]
            return {"devices": {"/device:TPU:0": ops}, "marks": self._marks}

    monkeypatch.setattr(runner, "Tracer", WholeSurveyBusy)
    from drynx_tpu.utils.timers import PROCESS
    before = PROCESS.counters()
    sut = _sut(device_facts=lambda: {"platform": "cpu",
                                     "kind": "TPU v5 lite", "count": 1})
    rc, line, _, _ = _run(small_copy, CELL, capsys, sut=sut, trace=True,
                          seconds=3600)
    assert rc == 0 and line["correct"] is True
    loaded = cells.load_cell(small_copy, CELL)
    assert line["attempted"] == loaded.config["traced_surveys"] == 1
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # on the CPU the store never engages: its two metrics read nothing
    assert set(metrics) == {m["name"] for m in loaded.per_layer} - {
        "setup.exec_store_hit_share", "setup.exec_store_load_s"}
    assert NEW_METRICS <= set(metrics)
    steps = ("step_s.dro.noise_enc", "step_s.dro.zero_enc",
             "step_s.dro.permute_add")
    assert all(metrics[s] > 0 for s in steps)
    assert sum(metrics[s] for s in steps) <= metrics["phase_s.dro"]
    assert metrics["device_glue_s.dro"] == 0.0      # the kernel covers it
    # the counter is the process's: this run's part of it is two surveys'
    after = PROCESS.counters()
    assert after["dro_encryptions"] - before.get("dro_encryptions", 0) \
        == 2 * 64 * (1 + 3)
    assert metrics["dro.encryptions_per_survey"] \
        == after["dro_encryptions"] / after["surveys"]
    # the phase's bytes over the chip's bandwidth, against the kernel
    # seconds inside the phase: the whole phase here
    least = (64 * 456 + 3 * 64 * 832) / 819e9
    assert metrics["kernel.dro_hbm_roofline"] == pytest.approx(
        100 * least / metrics["phase_s.dro"], rel=0.02)


def test_check_dro_checks_the_whole_list(small_copy, monkeypatch):
    config = cells.load_cell(small_copy, CELL).config
    lines = []
    assert check_dro.check_phase(config, 2 ** 31 + 3, _sut(),
                                 root=small_copy, note=lines.append)
    steps = [ln["step"] for ln in lines]
    assert steps == ["noise_values", "noise_enc", "node_pass", "node_pass",
                     "node_pass", "counter", "zero_enc_bytes", "control"]
    assert all(ln["held"] for ln in lines)
    for ln in lines[2:5]:
        assert ln["multiset_equal"] and ln["is_input_permuted"]
        assert ln["ciphertexts_unchanged"] == 0
        assert ln["permutation_fixed_points"] < 64
    assert lines[-1]["correct"] is False
    # a pass that re-randomises nothing is seen
    from drynx_tpu.parallel import dro
    monkeypatch.setattr(dro, "_dro_permute_add",
                        lambda cts, idx, zero: cts[idx])
    lines.clear()
    assert not check_dro.check_phase(config, 2 ** 31 + 3, _sut(),
                                     root=small_copy, note=lines.append)
    assert [ln["ciphertexts_unchanged"] for ln in lines[2:5]] == [64] * 3
