"""The reduction from a trace to numbers, on small recorded traces in the
neutral form (data/)."""
import json
import os

import pytest

from benchmarks.harness.trace import (TraceSummary, op_family, short_name,
                                      _union)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
G1 = r"^(_fixed_base_mul_flat|_scalar_mul_flat|_point_add_flat|" \
     r"_fp_inv_flat)(\.\d+)?( tpu_custom_call)?$"


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_small_trace_by_hand():
    s = TraceSummary(_load("trace_small.json"))
    assert s.window_s == pytest.approx(2000e-9)
    # busy: 1100-1500, 1600-1900 (the while overlaps the kernel's end),
    # 2200-2500, 2700-2800, 2950-3000 (clipped at the window's end)
    assert s.busy_s == pytest.approx((400 + 300 + 300 + 100 + 50) * 1e-9)
    assert s.seconds_matching(G1) == pytest.approx(900e-9)
    ops = dict(s.device_ops(10))
    assert ops["_fixed_base_mul_flat"] == pytest.approx(600e-9)
    assert ops["copy"] == pytest.approx(50e-9)
    assert "fusion" not in ops          # it ran before the first mark
    assert list(ops)[0] == "_fixed_base_mul_flat"


def test_idle_gaps_are_named_by_the_host_span_over_their_middle():
    s = TraceSummary(_load("trace_small.json"))
    spans = [("DataCollectionProtocol", 1000, 1550),
             ("KeySwitchingPhase", 1550, 1950),
             ("DataCollectionProtocol", 2000, 2600)]
    gaps = dict(s.idle_gaps(spans, 10))
    # 1000-1100 and 2000-2200 collect; 1500-1600 collect (middle 1550 is
    # the key switch's first instant); 1900-2200 is one gap whose middle
    # 2050 lies in the second collect
    assert gaps["DataCollectionProtocol"] == pytest.approx(400e-9)
    assert gaps["KeySwitchingPhase"] == pytest.approx(100e-9)
    assert gaps["between_phases"] == pytest.approx((200 + 150) * 1e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_names():
    hlo = ("%_scalar_mul_flat.1 = u32[3,16,4096]{2,1,0} custom-call(...), "
           "custom_call_target=\"tpu_custom_call\"")
    assert short_name(hlo) == "_scalar_mul_flat.1 tpu_custom_call"
    assert short_name("%fusion.3 = u32[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.3"
    assert short_name("while.2") == "while.2"
    assert op_family("_fixed_base_mul_flat.4 tpu_custom_call") \
        == "_fixed_base_mul_flat"
    assert op_family("add_bitcast_fusion") == "add_bitcast_fusion"
    assert _union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]


def test_a_trace_with_nothing_to_read_is_refused():
    with pytest.raises(RuntimeError, match="no survey mark"):
        TraceSummary({"devices": {"/device:TPU:0": []}, "marks": []})


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.startswith("trace_chip_")))
def test_recorded_chip_trace(name):
    """A cut of a real v5e trace (PERF.md says which run): the kernels are
    found by name and the busy time lies inside the window."""
    s = TraceSummary(_load(name))
    assert 0 < s.busy_s <= s.window_s
    assert 0 < s.seconds_matching(G1) <= s.busy_s
