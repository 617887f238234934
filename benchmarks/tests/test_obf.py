"""The obfuscated max (`max-grid-10dp-obf`): its data files, the plain
reference by a hand-worked case, the control, the bytes counted for the
phase's roofline, the plain-integer G1 of `check_obf.py`, that script's
pass-by-pass check, and the cell end to end through `run_cell`, all at a
small size of this file's own (`conftest.SMALL` has no entry for it)."""
import json
import os

import numpy as np
import pytest

from benchmarks import check_obf
from benchmarks.check_seeds import merge
from benchmarks.harness import cells, check, runner, window, work_obf
from conftest import ROOT
from test_rehearsal import _FakeTracer, _run, _sut

CELL = "max-grid-10dp-obf.one-querier"
NAME = "max-grid-10dp-obf"
# a 16-bucket grid over 4 providers: the CPU path takes seconds per hundred
# ciphertexts, and a jnp ladder a second a lane
SMALL = {"n_buckets": 16, "dlog_limit": 16, "roster": {"n_dps": 4}}
NEW_METRICS = {"phase_s.obfuscate", "step_s.obf.mul",
               "step_s.obf.randomness", "device_glue_s.obfuscate",
               "obf.scalar_muls_per_survey", "kernel.obf_hbm_roofline"}


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
    assert config["obfuscation"] is True
    assert config["roster"] == {"n_cns": 3, "n_dps": 10, "n_vns": 3}
    assert (config["query_min"], config["n_buckets"],
            config["values_per_dp"], config["dlog_limit"]) \
        == (0, 12288, 1, 10000)
    assert sorted(config["reduced"]) == ["n_buckets"]
    assert config["reduced"]["n_buckets"]["source"] == 1000000
    assert set(config["assumed"]) >= {"range", "values_per_dp",
                                      "value_range"}
    assert config["guarantees"] == {
        "every_dp_answers": True, "exact_zero_pattern": True,
        "every_cn_obfuscates": 3,
        "fresh_scalar_every_ciphertext_and_pass": True,
        "host_oracle_calls": 0}
    # a miss of the discrete-log table is what a non-zero bucket must
    # give: no `dlog_missed`, no `decrypted_diff_max`
    assert config["limits"] == {"zero_pattern_diff": 0,
                                "nonzero_resolved": 0, "answer_diff": 0,
                                "dps_missing": 0}
    assert config["control"] == {"reference": "obfuscation_off"}
    assert cell.traffic["warmup_surveys"] == 1
    for kind in ("datagen", "query", "reference"):
        assert cells.plugin(ROOT, {"query": "queries"}.get(kind, kind),
                            config[kind])
    # everything but the flag, the query maker, the reference and what
    # they bring is the grid configuration's: it is this cell's bypass
    grid = cells.load_cell(ROOT, "max-grid-10dp-exec.one-querier")
    same = ("op", "roster", "proofs", "query_min", "n_buckets",
            "values_per_dp", "dlog_limit", "traced_surveys", "datagen")
    assert {k: config[k] for k in same} == {k: grid.config[k] for k in same}
    per_layer = {m["name"]: m for m in cell.per_layer}
    assert NEW_METRICS <= set(per_layer)
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "survey_s.mean"
    others = {m["name"] for c in ("max-grid-10dp-exec.one-querier",
                                  "diffp-sum-10dp-exec.one-querier")
              for m in cells.load_cell(ROOT, c).per_layer}
    assert not others & NEW_METRICS
    assert not {n for n in per_layer if ".dro" in n or ".fused_" in n}


def test_the_query_asks_for_obfuscation():
    config = cells.load_cell(ROOT, CELL).config
    maker = cells.plugin(ROOT, "queries", config["query"])
    assert maker.query_kwargs(config, {}) == {
        "query_min": 0, "query_max": 12287, "obfuscation": True}
    assert maker.n_values(config) == 12288


def _reference():
    return cells.plugin(ROOT, "reference", "max_obf")


HAND = {"query_min": 0, "n_buckets": 6,
        "limits": {"zero_pattern_diff": 0, "nonzero_resolved": 0,
                   "answer_diff": 0, "dps_missing": 0}}
HAND_DATA = {"per_dp": [np.asarray([1]), np.asarray([4]), np.asarray([2])]}
MISS = [False] * 4 + [True] * 2     # what an obfuscated survey gives


@pytest.mark.parametrize("values,found,result,want", [
    # counts 3 2 1 1 0 0: the first four in no table, the last two zero
    ([0, 0, 0, 0, 0, 0], MISS, 4,
     {"zero_pattern_diff": 0, "nonzero_resolved": 0, "answer_diff": 0}),
    # whatever the table's miss leaves in `values` is not read
    ([7, -3, 9, 1, 0, 0], MISS, 4,
     {"zero_pattern_diff": 0, "nonzero_resolved": 0, "answer_diff": 0}),
    # a count came out: bucket 2 resolved to its clear count
    ([0, 0, 1, 0, 0, 0], [False, False, True, False, True, True], 4,
     {"zero_pattern_diff": 0, "nonzero_resolved": 1, "answer_diff": 0}),
    # a non-zero bucket that decrypts to zero: the pattern and the table
    ([0, 0, 0, 0, 0, 0], [False, False, False, True, True, True], 3,
     {"zero_pattern_diff": 1, "nonzero_resolved": 1, "answer_diff": 1}),
    # a zero bucket that did not decrypt to zero
    ([0, 0, 0, 0, 0, 0], [False] * 5 + [True], 5,
     {"zero_pattern_diff": 1, "nonzero_resolved": 0, "answer_diff": 1}),
    ([0, 0, 0, 0, 5, 0], [False] * 4 + [True] * 2, 5,
     {"zero_pattern_diff": 1, "nonzero_resolved": 0, "answer_diff": 1}),
    ([0, 0, 0, 0, 0, 0], MISS, None,
     {"zero_pattern_diff": 0, "nonzero_resolved": 0,
      "answer_diff": float("inf")}),
    ([0, 0, 0], [True] * 3, 4,
     {"zero_pattern_diff": 6, "nonzero_resolved": 6,
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


def test_the_control_comes_out_not_correct_by_one_limit_alone():
    ref = _reference()
    for config, data in ((HAND, HAND_DATA),
                         (cells.load_cell(ROOT, CELL).config, None)):
        if data is None:
            data = cells.plugin(ROOT, "datagen", config["datagen"]).generate(
                config, 2 ** 31 + 17)
        expected = ref.expect(config, data)
        counts = expected["decrypted"]
        good = {"values": np.zeros_like(counts), "found": counts == 0,
                "result": expected["answer"], "dps_missing": 0}
        fake = dict(ref.control(config, data, expected, "obfuscation_off"),
                    dps_missing=0)
        assert np.array_equal(fake["values"], counts) and fake["found"].all()
        verdicts = []
        for outputs in (good, fake):
            rec = window.SurveyRecord(0, 1, 0.0, 0.0, outputs, {}, [])
            compared = check.compare_window(config, ref, expected, [rec], 0)
            verdicts.append((check.verdict(compared), {
                k: c["value"] for k, c in compared.items()
                if c["value"] > c["limit"]}))
        assert verdicts[0] == (True, {})
        assert verdicts[1] == (False, {
            "nonzero_resolved": int((counts != 0).sum())})
    with pytest.raises(ValueError):
        ref.control(HAND, HAND_DATA, ref.expect(HAND, HAND_DATA), "other")


def test_the_phases_bytes_are_counted_from_its_sizes():
    config = cells.load_cell(ROOT, CELL).config
    assert work_obf.obf_bytes_per_survey(config, 12288) \
        == 3 * 12288 * (384 + 64 + 384) == 30670848
    small = merge(config, SMALL)
    assert work_obf.obf_bytes_per_survey(small, 16) == 3 * 16 * 832
    one_node = merge(config, {"roster": {"n_cns": 1}})
    assert work_obf.obf_bytes_per_survey(one_node, 12288) == 12288 * 832


# --- check_obf.py's own G1 ----------------------------------------------------

G2 = (4062534355977912733299777421397494108926584881726437723242321564179011504485,
      61953648928663169182821605676311785161130419446328175279445403853729925443418)
G7 = (27234185003945801561313803365459690380071781716715819452605766012790413899242,
      26491265192573786958641915180560764331683058370017348003360240308332128516715)
K_BIG = 12345678901234567890
G_BIG = (11516708230729735202354916007955291390475839712565835962591388761647488282594,
         39904540413256941974294842541156942324500541674439552660183998694165442380284)


def _affine_add(p, q):
    """The chord-and-tangent rule on affine points, written apart from the
    script's Jacobian formulas."""
    mod = check_obf.P
    (x1, y1), (x2, y2) = p, q
    if p == q:
        lam = 3 * x1 * x1 * pow(2 * y1, -1, mod) % mod
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, mod) % mod
    x3 = (lam * lam - x1 - x2) % mod
    return x3, (lam * (x1 - x3) - y1) % mod


def test_the_scripts_g1_gives_known_multiples_of_the_generator():
    g, mul = check_obf.GENERATOR, check_obf.g1_mul
    on_curve = lambda p: (p[1] ** 2 - p[0] ** 3 - 3) % check_obf.P == 0
    assert on_curve(g) and mul(g, 1) == g
    assert mul(g, 2) == G2 == _affine_add(g, g)
    assert mul(g, 3) == _affine_add(G2, g)
    assert mul(g, 7) == G7 and mul(g, K_BIG) == G_BIG
    assert on_curve(G7) and on_curve(G_BIG)
    # the group's order: n G is the identity, (n - 1) G is -G, n + 2 is 2
    n = check_obf.N
    assert mul(g, 0) is None and mul(g, n) is None and mul(None, 5) is None
    assert mul(g, n - 1) == (1, check_obf.P - 2)
    assert mul(g, n + 2) == G2
    # k (m G) = (k m) G, and the addition's special cases
    assert mul(G7, K_BIG) == mul(g, 7 * K_BIG) == mul(G_BIG, 7)
    j = lambda p: (p[0], p[1], 1)
    assert check_obf.g1_affine(check_obf.g1_add(j(g), j(g))) == G2
    assert check_obf.g1_add(j(g), j((1, check_obf.P - 2))) is None
    assert check_obf.g1_add(None, j(g)) == j(g)


def test_the_scripts_reading_of_a_device_point():
    """16 limbs of 16 bits, residues times 2^256, Z = 0 the identity."""
    shift = 1 << 256

    def limbs(v):
        v = v * shift % check_obf.P
        return [(v >> (16 * k)) & 0xFFFF for k in range(16)]

    assert check_obf.int_of_limbs(limbs(1)) == shift % check_obf.P
    z = 5                                   # a Jacobian form of 7 G
    point = np.asarray([limbs(G7[0] * z * z), limbs(G7[1] * z ** 3),
                        limbs(z)], dtype=np.uint32)
    assert check_obf.point_of_limbs(point) == G7
    assert check_obf.point_of_limbs(
        np.asarray([limbs(1), limbs(1), [0] * 16])) is None
    # and the program's own encoding of the same point reads the same
    from drynx_tpu.crypto import curve as C
    assert check_obf.point_of_limbs(C.from_ref(G7)) == G7
    assert check_obf.point_of_limbs(C.from_ref(None)) is None


# --- the system at a small size -----------------------------------------------

def test_two_surveys_end_to_end(small_copy, capsys):
    rc, line, earlier, err = _run(small_copy, CELL, capsys,
                                  seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is True, line["compared"]
    assert line["attempted"] == 1 and line["failed"] == 0
    assert set(line["compared"]) == {
        "zero_pattern_diff", "nonzero_resolved", "answer_diff",
        "dps_missing", "failed_surveys", "host_oracle_calls"}
    assert all(c["value"] == 0 for c in line["compared"].values())
    assert set(line["metrics"]) == {"survey_s.mean", "setup_s"}
    window_line = next(e for e in earlier if e["phase"] == "window")
    assert {"ObfuscationPhase", "ObfuscationPhase/randomness",
            "ObfuscationPhase/mul"} <= set(window_line["phase_s_mean"])
    reference = next(e for e in earlier if e["phase"] == "reference")
    assert reference["surveys_compared"] == 2


def test_a_survey_that_does_not_obfuscate_is_not_correct(small_copy,
                                                          capsys):
    """The reference against the system with the guarantee broken
    underneath: the grid's query maker in this one's place."""
    path = os.path.join(small_copy, "benchmarks", "configs", NAME + ".json")
    config = merge(cells.load_json(path), {"query": "grid"})
    with open(path, "w") as f:
        json.dump(config, f)
    rc, line, _, _ = _run(small_copy, CELL, capsys, seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is False
    over = {k for k, c in line["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {"nonzero_resolved"}


def test_traced_run_reports_the_new_per_layer_metrics(small_copy, capsys,
                                                      monkeypatch):
    class WholeSurveyBusy(_FakeTracer):
        """One kernel op over the whole of each survey, so that some of it
        lies inside the obfuscation phase."""

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
    steps = ("step_s.obf.randomness", "step_s.obf.mul")
    assert all(metrics[s] > 0 for s in steps)
    # the phase is its two steps, the three nodes' under one name each
    assert sum(metrics[s] for s in steps) <= metrics["phase_s.obfuscate"]
    assert sum(metrics[s] for s in steps) == pytest.approx(
        metrics["phase_s.obfuscate"], abs=0.005)
    assert metrics["device_glue_s.obfuscate"] == 0.0  # the kernel covers it
    # the counter is the process's: this run's part of it is two surveys'
    after = PROCESS.counters()
    assert after["obf_scalar_muls"] - before.get("obf_scalar_muls", 0) \
        == 2 * (2 * 16 * 3)
    assert metrics["obf.scalar_muls_per_survey"] \
        == after["obf_scalar_muls"] / after["surveys"]
    # the phase's bytes over the chip's bandwidth, against the kernel
    # seconds inside the phase: the whole phase here
    least = 3 * 16 * 832 / 819e9
    assert metrics["kernel.obf_hbm_roofline"] == pytest.approx(
        100 * least / metrics["phase_s.obfuscate"], rel=0.02)


def test_a_cell_that_does_not_obfuscate_reads_no_phase_roofline(bench_copy):
    reader = cells.plugin(ROOT, "readers", "obf_roofline")
    spec = cells.load_json(os.path.join(
        ROOT, "benchmarks", "metrics", "kernel.obf_hbm_roofline.json"))
    grid = cells.load_cell(bench_copy, "max-grid-10dp-exec.one-querier")
    ctx = runner.RunContext(grid, {"kind": "TPU v5 lite"}, [], 0.0, 1.0,
                            0.0, {}, {}, summary=object())
    assert reader.read(spec, ctx) is None
    ctx.summary = None
    ctx.cell = cells.load_cell(bench_copy, CELL)
    assert reader.read(spec, ctx) is None


def test_check_obf_checks_every_pass(small_copy, monkeypatch):
    config = cells.load_cell(small_copy, CELL).config
    lines = []
    assert check_obf.check_phase(config, 2 ** 31 + 3, _sut(),
                                 root=small_copy, note=lines.append)
    steps = [ln["step"] for ln in lines]
    assert steps == ["survey", "counter", "aggregate", "node_pass",
                     "node_pass", "node_pass", "scalars", "control"]
    assert all(ln["held"] for ln in lines)
    assert lines[0]["correct"] is True and lines[0]["passes"] == 3
    assert lines[1]["obf_scalar_muls"] == 2 * 16 * 3
    for ln in lines[3:6]:
        assert ln["sampled"] == 16 and ln["points_wrong"] == 0
        assert ln["ciphertexts_unchanged"] == 0
        assert ln["identity_pattern_diff"] == 0
        assert ln["input_is_the_pass_befores_output"]
    assert lines[6]["shared_between_nodes"] == 0
    assert lines[6]["distinct_within_node"] == [16] * 3
    assert lines[-1]["correct"] is False
    assert lines[-1]["over_their_limit"] == ["nonzero_resolved"]

    from drynx_tpu.parallel import obfuscation as obf
    real = obf._obf_scalar_mul
    # a pass that multiplies by other scalars than it hands back is seen ...
    monkeypatch.setattr(obf, "_obf_scalar_mul",
                        lambda cts, s: real(cts, s[::-1]))
    lines.clear()
    assert not check_obf.check_phase(config, 2 ** 31 + 3, _sut(),
                                     root=small_copy, note=lines.append)
    assert [ln["points_wrong"] > 0 for ln in lines[3:6]] == [True] * 3
    # ... and so is a pass that obfuscates nothing
    monkeypatch.setattr(obf, "_obf_scalar_mul", lambda cts, s: cts)
    lines.clear()
    assert not check_obf.check_phase(config, 2 ** 31 + 3, _sut(),
                                     root=small_copy, note=lines.append)
    assert lines[0]["correct"] is False
    assert [ln["ciphertexts_unchanged"] for ln in lines[3:6]] == [16] * 3
