"""The 48-computing-node max (`max-grid-48cn-exec`): its data files, the
query maker's refusal of a program without a node pass, the plain reference
by a hand-worked case, the control, the bytes counted for the phase's
roofline, the new reader on a recorded trace, `check_ks.py`'s node-by-node
check, and the cell end to end through `run_cell`, all at a small size of
this file's own (`conftest.SMALL` has no entry for it)."""
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmarks import check_ks
from benchmarks.check_seeds import merge
from benchmarks.harness import cells, check, runner, window, work_ks
from benchmarks.harness.trace import TraceSummary
from conftest import ROOT
from test_rehearsal import _FakeTracer, _run, _sut

CELL = "max-grid-48cn-exec.one-querier"
NAME = "max-grid-48cn-exec"
GRID = "max-grid-10dp-exec.one-querier"
# a 16-bucket grid over 4 providers and 5 computing nodes: the CPU path
# takes a quarter of a second a node's pass at this width
SMALL = {"n_buckets": 16, "dlog_limit": 16,
         "roster": {"n_dps": 4, "n_cns": 5},
         "guarantees": {"every_cn_switches": 5}}
NEW_METRICS = {"step_s.ks.pass", "step_s.ks.randomness",
               "ks.contributions_per_survey", "kernel.ks_hbm_roofline"}


@pytest.fixture
def small_copy(bench_copy):
    path = os.path.join(bench_copy, "benchmarks", "configs", NAME + ".json")
    config = merge(cells.load_json(path), SMALL)
    with open(path, "w") as f:
        json.dump(config, f)
    return bench_copy


def test_the_new_files_load_and_say_what_the_issue_says():
    cell = cells.load_cell(ROOT, CELL)
    config = cell.config
    assert cell.chips == 1 and config["name"] == NAME
    assert config["op"] == "max" and config["proofs"] == 0
    assert config["obfuscation"] is False
    assert config["roster"] == {"n_cns": 48, "n_dps": 10, "n_vns": 3}
    assert (config["query_min"], config["n_buckets"],
            config["values_per_dp"], config["dlog_limit"]) \
        == (0, 12288, 1, 10000)
    # the roster is the source's: only the grid is cut
    assert sorted(config["reduced"]) == ["n_buckets"]
    assert config["reduced"]["n_buckets"]["source"] == 1000000
    assert set(config["assumed"]) >= {"op_and_range", "n_dps",
                                      "values_per_dp", "value_range"}
    assert config["guarantees"] == {
        "every_dp_answers": True, "exact_result": True,
        "every_cn_switches": 48,
        "own_secret_and_fresh_scalars_every_cn": True,
        "host_oracle_calls": 0}
    assert config["limits"] == {"decrypted_diff_max": 0, "dlog_missed": 0,
                                "answer_diff": 0, "dps_missing": 0}
    assert config["control"] == {"reference": "drop_one_cn"}
    assert cell.traffic["warmup_surveys"] == 1
    for kind in ("datagen", "query", "reference"):
        assert cells.plugin(ROOT, {"query": "queries"}.get(kind, kind),
                            config[kind])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["n_buckets"]
    # everything but the roster, the query maker and the reference is the
    # grid configuration's: it is this cell's bypass
    grid = cells.load_cell(ROOT, GRID)
    same = ("op", "proofs", "query_min", "n_buckets", "values_per_dp",
            "dlog_limit", "traced_surveys", "datagen", "limits")
    assert {k: config[k] for k in same} == {k: grid.config[k] for k in same}
    assert {k: v for k, v in config["roster"].items() if k != "n_cns"} \
        == {k: v for k, v in grid.config["roster"].items() if k != "n_cns"}
    per_layer = {m["name"]: m for m in cell.per_layer}
    assert NEW_METRICS <= set(per_layer)
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "survey_s.mean"
    assert {"phase_s.keyswitch", "device_glue_s.keyswitch",
            "kernel.g1_s_per_survey", "kernel.g1_hbm_roofline"} \
        <= set(per_layer)
    others = {m["name"] for w in bench["workloads"] if w["name"] != CELL
              for m in cells.load_cell(ROOT, w["name"]).per_layer}
    assert not others & NEW_METRICS
    assert not {n for n in per_layer
                if ".dro" in n or ".obf" in n or ".fused_" in n}


# --- the query maker, and the parent's clean refusal ----------------------------

def test_the_query_is_the_grids():
    config = cells.load_cell(ROOT, CELL).config
    maker = cells.plugin(ROOT, "queries", config["query"])
    grid = cells.plugin(ROOT, "queries", "grid")
    assert maker.query_kwargs(config, {}) == grid.query_kwargs(config, {}) \
        == {"query_min": 0, "query_max": 12287}
    assert maker.n_values(config) == grid.n_values(config) == 12288


@pytest.mark.parametrize("program", ["no_module", "no_programs"])
def test_a_program_without_a_node_pass_is_refused_in_set_up(monkeypatch,
                                                            program):
    """What the parent commit does in this cell: a RuntimeError out of the
    query maker, before a cluster is built or anything traced."""
    config = cells.load_cell(ROOT, CELL).config
    maker = cells.plugin(ROOT, "queries", config["query"])
    name = "drynx_tpu.parallel.keyswitch"
    if program == "no_module":
        monkeypatch.setitem(sys.modules, name, None)    # import raises
    else:
        monkeypatch.setitem(sys.modules, name, types.ModuleType(name))
    with pytest.raises(RuntimeError) as refused:
        maker.query_kwargs(config, {})
    assert "589824 lanes" in str(refused.value)
    assert "48 computing nodes x 12288 buckets" in str(refused.value)


def test_the_refusal_ends_a_run_before_the_cluster(small_copy, capsys,
                                                   monkeypatch):
    monkeypatch.setitem(sys.modules, "drynx_tpu.parallel.keyswitch", None)
    built = []
    sut = _sut(System=lambda *a, **k: built.append(a))
    with pytest.raises(RuntimeError):
        _run(small_copy, CELL, capsys, sut=sut)
    assert not built


# --- the reference and its control ------------------------------------------------

def _reference():
    return cells.plugin(ROOT, "reference", "max_cns")


HAND = {"query_min": 0, "n_buckets": 6,
        "limits": {"decrypted_diff_max": 0, "dlog_missed": 0,
                   "answer_diff": 0, "dps_missing": 0}}
HAND_DATA = {"per_dp": [np.asarray([1]), np.asarray([4]), np.asarray([2])]}
ALL = [True] * 6


@pytest.mark.parametrize("values,found,result,want", [
    ([3, 2, 1, 1, 0, 0], ALL, 4,
     {"decrypted_diff_max": 0, "dlog_missed": 0, "answer_diff": 0}),
    ([3, 2, 2, 1, 0, 0], ALL, 4,
     {"decrypted_diff_max": 1, "dlog_missed": 0, "answer_diff": 0}),
    ([3, 2, 1, 0, 0, 0], ALL, 3,
     {"decrypted_diff_max": 1, "dlog_missed": 0, "answer_diff": 1}),
    # a bucket the table missed is counted, and its value not read
    ([3, 2, 9, 1, 0, 0], [True, True, False, True, True, True], 4,
     {"decrypted_diff_max": 0, "dlog_missed": 1, "answer_diff": 0}),
    ([0] * 6, [False] * 6, 4,
     {"decrypted_diff_max": 0, "dlog_missed": 6, "answer_diff": 0}),
    ([3, 2, 1, 1, 0, 0], ALL, None,
     {"decrypted_diff_max": 0, "dlog_missed": 0,
      "answer_diff": float("inf")}),
    ([3, 2, 1], [True] * 3, 4,
     {"decrypted_diff_max": float("inf"), "dlog_missed": 6,
      "answer_diff": float("inf")}),
])
def test_compare_by_hand(values, found, result, want):
    ref = _reference()
    expected = ref.expect(HAND, HAND_DATA)
    assert expected["decrypted"].tolist() == [3, 2, 1, 1, 0, 0]
    assert expected["answer"] == 4
    out = {"values": np.asarray(values), "found": np.asarray(found),
           "result": result}
    assert ref.compare(HAND, expected, out) == want


def test_the_answer_does_not_depend_on_the_roster():
    ref, grid_ref = _reference(), cells.plugin(ROOT, "reference", "max")
    config = cells.load_cell(ROOT, CELL).config
    data = cells.plugin(ROOT, "datagen", config["datagen"]).generate(
        config, 2 ** 31 + 17)
    ours = ref.expect(config, data)
    theirs = grid_ref.expect(cells.load_cell(ROOT, GRID).config, data)
    assert np.array_equal(ours["decrypted"], theirs["decrypted"])
    assert ours["answer"] == theirs["answer"]


def test_the_control_comes_out_not_correct_by_one_limit_alone():
    ref = _reference()
    for config, data in ((HAND, HAND_DATA),
                         (cells.load_cell(ROOT, CELL).config, None)):
        if data is None:
            data = cells.plugin(ROOT, "datagen", config["datagen"]).generate(
                config, 2 ** 31 + 17)
        expected = ref.expect(config, data)
        counts = expected["decrypted"]
        good = {"values": counts.copy(),
                "found": np.ones(counts.shape, dtype=bool),
                "result": expected["answer"], "dps_missing": 0}
        fake = dict(ref.control(config, data, expected, "drop_one_cn"),
                    dps_missing=0)
        assert not fake["found"].any()
        verdicts = []
        for outputs in (good, fake):
            rec = window.SurveyRecord(0, 1, 0.0, 0.0, outputs, {}, [])
            compared = check.compare_window(config, ref, expected, [rec], 0)
            verdicts.append((check.verdict(compared), {
                k: c["value"] for k, c in compared.items()
                if c["value"] > c["limit"]}))
        assert verdicts[0] == (True, {})
        assert verdicts[1] == (False, {"dlog_missed": int(counts.size)})
    with pytest.raises(ValueError):
        ref.control(HAND, HAND_DATA, ref.expect(HAND, HAND_DATA), "other")


# --- the phase's bytes and its reader ---------------------------------------------

def test_the_phases_bytes_are_counted_from_its_sizes():
    config = cells.load_cell(ROOT, CELL).config
    assert work_ks.ks_bytes_per_survey(config, 12288) \
        == 48 * 12288 * (192 + 64 + 2 * 192) == 377487360
    small = merge(config, SMALL)
    assert work_ks.ks_bytes_per_survey(small, 16) == 5 * 16 * 640
    grid = cells.load_cell(ROOT, GRID).config
    assert work_ks.ks_bytes_per_survey(grid, 12288) == 3 * 12288 * 640


def _ctx_over(trace: dict, phase_share: float):
    """A run of the cell whose one traced survey is the recorded trace's,
    with KeySwitchingPhase over the last `phase_share` of it."""
    (name, start, dur), = trace["marks"]
    index = int(name.rsplit(":", 1)[1])
    seconds = dur / 1e9
    spans = [("DataCollectionProtocol", 50.0,
              50.0 + (1 - phase_share) * seconds),
             ("KeySwitchingPhase", 50.0 + (1 - phase_share) * seconds,
              50.0 + seconds)]
    rec = window.SurveyRecord(index, 7, 50.0, 50.0 + seconds, {}, {}, spans)
    return runner.RunContext(
        cells.load_cell(ROOT, CELL), {"kind": "TPU v5 lite"}, [rec], 50.0,
        50.0 + seconds, 0.0, {}, {}, summary=TraceSummary(trace))


def test_the_reader_on_a_recorded_chip_trace():
    """A cut of a real v5e trace of the grid cell: the kernels inside the
    span are found by name, their seconds counted once, and the share is
    the phase's bytes over the chip's bandwidth against them."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "data",
                           "trace_chip_grid.json")) as f:
        trace = json.load(f)
    reader = cells.plugin(ROOT, "readers", "ks_roofline")
    spec = cells.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "kernel.ks_hbm_roofline.json"))
    whole, half = _ctx_over(trace, 1.0), _ctx_over(trace, 0.5)
    g1 = whole.summary.seconds_matching(spec["kernel_pattern"])
    phase = cells.plugin(ROOT, "readers", "phase_device").phase_spans_ns(
        whole, "KeySwitchingPhase")
    assert reader.kernel_seconds(whole, phase, spec["kernel_pattern"]) \
        == pytest.approx(g1, rel=1e-6)
    least = 377487360 / 819e9
    assert reader.read(spec, whole) == pytest.approx(100 * least / g1)
    assert 0 < reader.read(spec, whole) < 100
    # half the phase holds fewer kernel seconds: a larger share
    assert reader.read(spec, half) > reader.read(spec, whole)
    # nothing to read: no trace, no such span, no kernel inside it
    assert reader.read(dict(spec, phase="ObfuscationPhase"), whole) is None
    assert reader.read(dict(spec, kernel_pattern="^no_such_op$"),
                       whole) is None
    whole.summary = None
    assert reader.read(spec, whole) is None


# --- the system at a small size -----------------------------------------------------

def test_two_surveys_end_to_end(small_copy, capsys):
    rc, line, earlier, err = _run(small_copy, CELL, capsys,
                                  seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is True, line["compared"]
    assert line["attempted"] == 1 and line["failed"] == 0
    assert set(line["compared"]) == {
        "decrypted_diff_max", "dlog_missed", "answer_diff", "dps_missing",
        "failed_surveys", "host_oracle_calls"}
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert set(line["metrics"]) == {"survey_s.mean", "setup_s"}
    window_line = next(e for e in earlier if e["phase"] == "window")
    assert {"KeySwitchingPhase", "KeySwitchingPhase/secrets",
            "KeySwitchingPhase/randomness", "KeySwitchingPhase/pass",
            "KeySwitchingPhase/finish"} <= set(window_line["phase_s_mean"])
    reference = next(e for e in earlier if e["phase"] == "reference")
    assert reference["surveys_compared"] == 2


def test_a_survey_with_a_pass_left_out_is_not_correct(small_copy, capsys,
                                                      monkeypatch):
    """The reference against the system with the guarantee broken
    underneath: the roster's last node never contributes."""
    from drynx_tpu.parallel import keyswitch as kswitch
    real = kswitch.node_pass
    made = []

    def all_but_the_last(key, K0, x, q_tbl, acc=None, tm=None):
        made.append(1)
        if len(made) % 5 == 0:
            return acc, (None, None, None)
        return real(key, K0, x, q_tbl, acc, tm=tm)

    monkeypatch.setattr(kswitch, "node_pass", all_but_the_last)
    rc, line, _, _ = _run(small_copy, CELL, capsys, seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is False
    over = {k: c["value"] for k, c in line["compared"].items()
            if c["value"] > c["limit"]}
    # what the control says such a system gives: no bucket resolves (the
    # program's decode of a vector with nothing found is its own affair)
    assert over.pop("dlog_missed") == 16
    assert set(over) <= {"answer_diff"}


def test_traced_run_reports_the_new_per_layer_metrics(small_copy, capsys,
                                                      monkeypatch):
    class WholeSurveyBusy(_FakeTracer):
        """One kernel op over the whole of each survey, so that some of it
        lies inside the key switch."""

        def load(self):
            ops = [["_scalar_mul_flat.1 tpu_custom_call", s, d]
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
    steps = ("step_s.ks.randomness", "step_s.ks.pass")
    assert all(metrics[s] > 0 for s in steps)
    # the five nodes' steps under one name each, inside the phase
    assert sum(metrics[s] for s in steps) <= metrics["phase_s.keyswitch"]
    assert metrics["device_glue_s.keyswitch"] == 0.0  # the kernel covers it
    # the counter is the process's: this run's part of it is two surveys'
    after = PROCESS.counters()
    assert after["ks_contributions"] - before.get("ks_contributions", 0) \
        == 2 * (16 * 5)
    assert metrics["ks.contributions_per_survey"] \
        == after["ks_contributions"] / after["surveys"]
    # the phase's bytes over the chip's bandwidth, against the kernel
    # seconds inside the phase: the whole phase here
    least = 5 * 16 * 640 / 819e9
    assert metrics["kernel.ks_hbm_roofline"] == pytest.approx(
        100 * least / metrics["phase_s.keyswitch"], rel=0.02)


def test_check_ks_checks_every_node(small_copy, monkeypatch):
    config = cells.load_cell(small_copy, CELL).config
    lines = []
    assert check_ks.check_phase(config, 2 ** 31 + 3, _sut(),
                                root=small_copy, note=lines.append)
    steps = [ln["step"] for ln in lines]
    assert steps == ["survey", "counter"] + ["node_pass"] * 5 \
        + ["scalars", "one_contribution_left_out", "control"]
    assert all(ln["held"] for ln in lines)
    assert lines[0]["correct"] is True and lines[0]["passes"] == 5
    assert lines[1]["ks_contributions"] == 16 * 5
    for ln in lines[2:7]:
        assert ln["sampled"] == 16
        assert ln["points_wrong"] == ln["sums_wrong"] == 0
        assert ln["own_secret"] and ln["handed_the_pass_befores_sums"]
        assert ln["distinct_scalars"] == 16
    assert lines[7]["shared_between_nodes"] == 0
    assert lines[8]["resolved_with_all"] == 16
    assert lines[8]["resolved_without_the_last"] == 0
    assert lines[-1]["correct"] is False
    assert lines[-1]["over_their_limit"] == ["dlog_missed"]

    from drynx_tpu.parallel import keyswitch as kswitch
    real = kswitch._ks_pass
    # a pass made with another secret than the node's own is seen, though
    # the survey it spoils is what gives it away first
    monkeypatch.setattr(
        kswitch, "_ks_pass",
        lambda q_tbl, K0, x, r, k_sum, c_sum:
        real(q_tbl, K0, x.at[0].add(1), r, k_sum, c_sum))
    lines.clear()
    assert not check_ks.check_phase(config, 2 ** 31 + 3, _sut(),
                                    root=small_copy, note=lines.append)
    assert lines[0]["correct"] is False
    assert [ln["points_wrong"] > 0 for ln in lines[2:7]] == [True] * 5
    # ... and so are scalars other than the ones the pass hands back
    monkeypatch.setattr(
        kswitch, "_ks_pass",
        lambda q_tbl, K0, x, r, k_sum, c_sum:
        real(q_tbl, K0, x, r[::-1], k_sum, c_sum))
    lines.clear()
    assert not check_ks.check_phase(config, 2 ** 31 + 3, _sut(),
                                    root=small_copy, note=lines.append)
    assert lines[0]["correct"] is True      # the switch itself still holds
    assert [ln["points_wrong"] > 0 for ln in lines[2:7]] == [True] * 5
