"""The readers of the program's own tracer, and the check of its profiler
annotations: `phase_device` on a small hand-made trace, `process_spans` on a
stub tracer, `check_annotations.py`'s reduction on a hand-made list and on
a trace the profiler really wrote."""
import collections
import time
import types

import pytest

from benchmarks import check_annotations as ca
from benchmarks.harness import cells, window
from benchmarks.harness.trace import TraceSummary
from conftest import ROOT

S = 1_000_000_000       # a second of the trace's clock
KERNELS = cells.load_json(
    f"{ROOT}/benchmarks/metrics/device_glue_s.collect.json")["kernel_pattern"]


def _reader(name):
    return cells.plugin(ROOT, "readers", name)


# --- phase_device -----------------------------------------------------------

def _traced_ctx():
    """One traced survey, submitted at perf_counter 100.0 and marked at 1 s
    of the trace's clock: phase A over [2, 5] s, phase B over [6, 10] s."""
    ops = [
        ["_scalar_mul_flat.1 tpu_custom_call", 2 * S + S // 2, S],  # A: kernel
        ["copy.1", 4 * S + S // 2, S],          # glue, half of it inside A
        ["fusion.9", 5 * S + S // 5, S // 4],   # between the phases
        ["while.3", 6 * S + S // 2, 2 * S],     # B: holds the next two
        ["add_bitcast_fusion.2", 7 * S, S],
        ["_fixed_base_mul_flat.4 tpu_custom_call", 7 * S + S // 5, S // 2],
    ]
    neutral = {"devices": {"/device:TPU:0": ops},
               "marks": [["bench:survey:1", 1 * S, 10 * S]]}
    spans = [("A", 101.0, 104.0), ("A/step", 101.0, 102.0),
             ("B", 105.0, 109.0)]
    rec = window.SurveyRecord(1, 7, 100.0, 110.0, {}, {}, spans)
    return types.SimpleNamespace(summary=TraceSummary(neutral), records=[rec])


@pytest.mark.parametrize("phase, glue", [
    ("A", 0.5),     # half of the copy; the kernel is taken out
    ("B", 1.5),     # the while's 2 s once, its fusion not again, less 0.5
])
def test_phase_device_counts_the_union_once(phase, glue):
    spec = {"phase": phase, "kernel_pattern": KERNELS}
    assert _reader("phase_device").read(spec, _traced_ctx()) \
        == pytest.approx(glue)


def test_phase_device_reads_zero_or_nothing():
    reader, ctx = _reader("phase_device"), _traced_ctx()
    only_kernels = {"phase": "A", "kernel_pattern": "."}
    assert reader.read(only_kernels, ctx) == 0.0        # a reading
    assert reader.read({"phase": "C", "kernel_pattern": KERNELS}, ctx) is None
    ctx.summary = None
    assert reader.read({"phase": "A", "kernel_pattern": KERNELS}, ctx) is None


# --- process_spans ----------------------------------------------------------

Span = collections.namedtuple("Span", "name t0 t1")


class _StubTracer:
    def __init__(self, spans, counts):
        self._spans, self._counts = spans, counts

    def records(self, prefix=""):
        return [s for s in self._spans if s.name.startswith(prefix)]

    def counters(self):
        return dict(self._counts)

    def folded(self):
        return {"jax/trace:_fp_inv_flat": (3, 0.002),
                "jax/trace:bitwise_and": (55051, 0.5)}


@pytest.fixture
def stub(monkeypatch):
    reader = _reader("process_spans")
    spans = [Span("jax/trace:_fused_ks", 10.0, 71.0),
             Span("jax/trace:_scalar_mul_flat", 20.0, 25.0),
             Span("jax/trace:_scalar_mul_flat", 30.0, 36.0),
             Span("jax/trace:_fused_ks", 80.0, 82.0),     # another shape
             Span("jax/trace:_fused_ks_not", 90.0, 99.9),
             Span("jax/lower:_fused_ks", 100.0, 139.0),
             Span("jax/trace:train", 205.0, 205.5),       # inside the window
             Span("jax/trace:_fused_ks", 240.0, 390.0)]   # after it
    tracer = _StubTracer(spans, {"h2d_bytes": 300, "d2h_bytes": 200,
                                 "surveys": 4})
    monkeypatch.setattr(reader, "tracer", lambda: tracer)
    ctx = types.SimpleNamespace(t_open=200.0, t_close=220.0,
                                records=[object(), object()])
    return reader, ctx, tracer


@pytest.mark.parametrize("spec, value", [
    ({"reduce": "longest_s", "during": "setup",
      "names": ["jax/trace:_fused_ks"]}, 61.0),
    ({"reduce": "longest_s", "during": "setup",
      "names": ["jax/lower:_fused_ks"]}, 39.0),
    ({"reduce": "longest_s", "during": "setup",
      "names": ["jax/lower:_fused_dec"]}, None),
    ({"reduce": "count", "during": "setup",
      "names": ["jax/trace:_scalar_mul_flat"]}, 2),
    ({"reduce": "count", "during": "setup",     # two kept, three folded
      "names": ["jax/trace:_scalar_mul_flat", "jax/trace:_fp_inv_flat"]}, 5),
    ({"reduce": "count", "during": "setup", "prefix": "jax/lower:"}, 1),
    ({"reduce": "count", "during": "setup", "prefix": "jax/trace:_f"}, 6),
    ({"reduce": "count", "during": "setup",
      "names": ["jax/trace:_point_add_flat"]}, 0),
    ({"reduce": "count_per_survey", "during": "window",
      "prefix": "jax/trace:"}, 0.5),
    ({"reduce": "counter_ratio", "counters": ["h2d_bytes", "d2h_bytes"],
      "per": "surveys"}, 125.0),
    ({"reduce": "counter_ratio", "counters": ["h2d_bytes"],
      "per": "tiles"}, None),
])
def test_process_spans_reductions(stub, spec, value):
    reader, ctx, _ = stub
    assert reader.read(spec, ctx) == value


def test_process_spans_without_a_tracer_reads_nothing(monkeypatch):
    reader = _reader("process_spans")
    monkeypatch.setattr(reader, "tracer", lambda: None)
    spec = {"reduce": "count", "during": "setup", "prefix": "jax/"}
    assert reader.read(spec, types.SimpleNamespace(t_open=1.0)) is None


def test_process_spans_finds_the_programs_tracer():
    process = _reader("process_spans").tracer()
    assert process is not None
    assert isinstance(process.counters(), dict)
    assert all(len(r) >= 3 for r in process.records("jax/"))
    assert isinstance(process.folded(), dict)


def test_every_process_spans_metric_names_what_the_listener_writes():
    import glob

    for path in glob.glob(f"{ROOT}/benchmarks/metrics/*.json"):
        spec = cells.load_json(path)
        if spec["reader"] != "process_spans":
            continue
        for name in spec.get("names", [spec.get("prefix", "jax/trace:")]):
            assert name.startswith(("jax/trace:", "jax/lower:")), path
        assert spec["reduce"] == "counter_ratio" \
            or spec["during"] in ("setup", "window")


# --- check_annotations ------------------------------------------------------

def test_annotations_are_summed_by_name_and_matched_to_the_nearest():
    annotations = [["probe", 10, 5],
                   ["DataCollectionProtocol", 1000, 500],
                   ["DataCollectionProtocol/enc", 1100, 300],
                   ["DataCollectionProtocol", 5000, 700],
                   ["DataCollectionProtocol/enc", 5100, 350]]
    summed = ca.by_name(annotations)
    assert {k: v["count"] for k, v in summed.items()} == {
        "probe": 1, "DataCollectionProtocol": 2,
        "DataCollectionProtocol/enc": 2}
    assert summed["DataCollectionProtocol/enc"]["seconds"] \
        == pytest.approx(650e-9)
    moved = [("DataCollectionProtocol", 4990, 5720),
             ("DataCollectionProtocol", 1003, 1499),
             ("Decryption", 7000, 8000)]
    rows = ca.against_offsets(annotations, moved)
    assert rows[0]["annotation"] == [5000, 5700]
    assert rows[0]["start_diff_ms"] == pytest.approx(10 / 1e6)
    assert rows[0]["end_diff_ms"] == pytest.approx(-20 / 1e6)
    assert rows[1]["annotation"] == [1000, 1500]
    assert rows[2] == {"name": "Decryption", "annotation": None}


def test_the_programs_spans_are_found_in_a_trace_the_profiler_wrote(tmp_path):
    import jax.profiler as jp

    from drynx_tpu.utils.timers import PhaseTimers

    options = jp.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jp.start_trace(str(tmp_path), profiler_options=options)
    try:
        tm = PhaseTimers("survey-x")
        tm.start("KeySwitchingPhase")
        with tm.step("switch"):
            time.sleep(0.01)
        tm.end("KeySwitchingPhase")
    finally:
        jp.stop_trace()
    annotations, where = ca.host_annotations(ca.find_trace(str(tmp_path)))
    assert [a[0] for a in annotations] == ["KeySwitchingPhase",
                                           "KeySwitchingPhase/switch"]
    assert where and all(plane.startswith("/host:") for plane, _ in where)
    # the annotation and the span are one interval on two clocks
    (phase,) = [r for r in tm.records() if r.name == "KeySwitchingPhase"]
    assert annotations[0][2] / 1e9 == pytest.approx(phase.t1 - phase.t0,
                                                    abs=1e-3)
