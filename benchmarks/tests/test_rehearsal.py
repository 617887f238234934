"""Each cell end to end on the CPU at a small size, through the harness's
own `run_cell` with the look for a chip skipped; the same with the timed
path broken underneath, which has to come out as not correct; and a new
cell and a new per-layer metric added as files in a temporary copy."""
import json
import os
import types

import numpy as np
import pytest

from benchmarks.harness import cells, runner
from benchmarks.harness import sut as real_sut

CELLS = ["max-grid-10dp-exec.one-querier", "pima-logreg-10dp-exec.one-querier"]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _sut(**overrides):
    """The real system-under-test module with pieces swapped. On the CPU
    the program routes some G1 ops to its host oracle by design, so the
    rehearsal counts oracle calls from its own start."""
    ns = types.SimpleNamespace(**{k: getattr(real_sut, k)
                                  for k in dir(real_sut)
                                  if not k.startswith("_")})
    ns.host_oracle_calls = lambda: 0
    for k, v in overrides.items():
        setattr(ns, k, v)
    return ns


def _run(root, cell, capsys, sut=None, trace=False, seconds=0.01, seed=7):
    rc = runner.run_cell(root, cell, seed, seconds, trace, 0.0,
                         sut=sut or _sut(), require_chip=False)
    out = capsys.readouterr()
    lines = [ln for ln in out.out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), [json.loads(ln) for ln in lines[:-1]], \
        out.err


@pytest.mark.parametrize("cell", CELLS)
def test_two_surveys_end_to_end(bench_copy, capsys, cell):
    # one warm-up survey and one in the window, both compared
    rc, line, earlier, err = _run(bench_copy, cell, capsys,
                                  seed=2 ** 31 + 11)
    assert rc == 0
    assert set(line) == CONTRACT_KEYS | {"compared"}
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] == 1 and line["failed"] == 0
    wanted = {m["name"] for m in cells.load_cell(bench_copy, cell).end_to_end}
    assert set(line["metrics"]) == wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    reference = next(e for e in earlier if e["phase"] == "reference")
    assert reference["surveys_compared"] == 2
    assert err.rstrip().splitlines()[-1].startswith("compared ")
    assert "limit=" in err.rstrip().splitlines()[-1]


class _FakeTracer:
    """Stands in for the profiler, which has no device plane on the CPU:
    every mark becomes a host span, with one kernel op inside it that is
    busy for its middle half."""

    def __init__(self, out_dir):
        self.offsets, self._marks = {}, []

    def start(self):
        pass

    def stop(self):
        pass

    def annotate(self, index):
        import contextlib
        import time

        @contextlib.contextmanager
        def mark():
            t0 = self.offsets[index] = time.perf_counter()
            yield
            dur = time.perf_counter() - t0
            self._marks.append([f"bench:survey:{index}", int(t0 * 1e9),
                                int(dur * 1e9)])
        return mark()

    def load(self):
        ops = [["_scalar_mul_flat.1 tpu_custom_call", s + d // 4, d // 2]
               for _, s, d in self._marks]
        return {"devices": {"/device:TPU:0": ops}, "marks": self._marks}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_the_per_layer_metrics(bench_copy, capsys, cell,
                                                  monkeypatch):
    monkeypatch.setattr(runner, "Tracer", _FakeTracer)
    # the peaks table refuses a device it does not know: name the chip
    sut = _sut(device_facts=lambda: {"platform": "cpu",
                                     "kind": "TPU v5 lite", "count": 1})
    rc, line, _, _ = _run(bench_copy, cell, capsys, sut=sut, trace=True,
                          seconds=3600)
    loaded = cells.load_cell(bench_copy, cell)
    assert rc == 0 and line["correct"] is True
    assert line["attempted"] == loaded.config["traced_surveys"]
    assert set(line["metrics"]) == {m["name"] for m in loaded.per_layer}
    assert 50.0 <= line["metrics"]["device.idle_pct"]["value"] < 65.0
    assert 0 < line["metrics"]["kernel.g1_hbm_roofline"]["value"] < 100
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] > line["device"]["busy_s"]
    assert line["breakdown"]["device_ops"][0][0] == "_scalar_mul_flat"
    assert {g[0] for g in line["breakdown"]["idle_gaps"]} \
        <= {"DataCollectionProtocol", "AggregationPhase", "Decryption",
            "KeySwitchingPhase", "GradientDescent", "between_phases",
            "between_surveys"}


class _HalfTheProviders(real_sut.System):
    """Half of the roster left out: the survey is asked of the first half
    only, and accepts that quorum."""

    def new_query(self):
        half = len(self.roster) // 2
        return self.cluster.generate_survey_query(
            self._op, min_dp_quorum=half, **self._kwargs)

    def run(self, query, seed):
        half = self.roster[:len(self.roster) // 2]
        return self.cluster.finalize_survey(self.cluster.execute_survey(
            query, seed, responders=half))


def _altered(result, roster):
    out = real_sut.outputs_of(result, roster)
    out["values"] = out["values"].copy()
    out["values"][0] += 1
    return out


def _raises(self, query, seed):
    raise RuntimeError("planted: the survey fails")


FAULTS = {
    "half_of_the_providers_left_out": dict(System=_HalfTheProviders),
    "an_answer_altered": dict(outputs_of=_altered),
    "a_survey_that_raises": dict(System=type(
        "Raising", (real_sut.System,), {"run": _raises})),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(bench_copy, capsys, cell, fault):
    rc, line, _, _ = _run(bench_copy, cell, capsys, sut=_sut(**FAULTS[fault]))
    assert rc == 0
    assert line["correct"] is False
    over = [k for k, c in line["compared"].items() if c["value"] > c["limit"]]
    assert over, line["compared"]


def test_host_oracle_calls_fail_the_run(bench_copy, capsys):
    _, line, _, _ = _run(bench_copy, CELLS[0], capsys,
                         sut=_sut(host_oracle_calls=lambda: 2))
    assert line["correct"] is False
    assert line["compared"]["host_oracle_calls"] == {"value": 2, "limit": 0}


def test_a_cell_and_a_metric_are_added_as_files(bench_copy, capsys,
                                                monkeypatch):
    """A third cell of an existing configuration, and a new per-layer
    metric with its reader: new files and one new entry each, no edit of a
    file that was there."""
    here = os.path.join(bench_copy, "benchmarks")
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(here) for p in fs}
    with open(os.path.join(here, "traffic", "one-querier-cold.json"),
              "w") as f:
        json.dump({"warmup_surveys": 0}, f)
    new_cell = "max-grid-10dp-exec.one-querier-cold"
    with open(os.path.join(here, "readers", "count.py"), "w") as f:
        f.write("def read(spec, ctx):\n    return len(ctx.records) or None\n")
    with open(os.path.join(here, "metrics", "window.surveys.json"), "w") as f:
        json.dump({"reader": "count", "layer": "survey orchestration",
                   "unit": "1", "moves": "survey_s.mean"}, f)
    manifest = os.path.join(bench_copy, "BENCHMARK.json")
    bench = cells.load_json(manifest)
    bench["workloads"].append({"name": new_cell,
                               "config": "max-grid-10dp-exec",
                               "traffic": "one-querier-cold", "chips": 1,
                               "why": "no warm-up: the first survey compiles"})
    bench["per_layer"].append({"name": "window.surveys", "unit": "1",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "survey orchestration",
                               "moves": "survey_s.mean",
                               "workloads": [new_cell]})
    with open(manifest, "w") as f:
        json.dump(bench, f)

    monkeypatch.setattr(runner, "Tracer", _FakeTracer)
    sut = _sut(device_facts=lambda: {"platform": "cpu",
                                     "kind": "TPU v5 lite", "count": 1})
    rc, line, earlier, _ = _run(bench_copy, new_cell, capsys, sut=sut,
                                trace=True)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["window.surveys"] == {"value": 1, "unit": "1"}
    old = cells.load_cell(bench_copy, CELLS[0])
    assert "window.surveys" not in {m["name"] for m in old.per_layer}
    assert not any(e["phase"] == "warmup" for e in earlier)
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _, fs in os.walk(here) for p in fs}
    assert all(after[p] == text for p, text in before.items())
    assert len(after) == len(before) + 3
