"""One run of one cell: set-up, window, memory reading, comparison, line.

Order, and why:
  1. set-up (counted in setup_s, from process start to the first timed
     survey): device check, compile cache, seeded rows, the cluster, the
     mix's warm-up surveys at the cell's own shapes;
  2. the window (window.py); in a --trace 1 run the profiler is on and the
     window is the configuration's `traced_surveys` surveys, or --seconds
     if that comes first;
  3. the device's memory counters are read and the cluster dropped BEFORE
  4. the plain reference runs and every survey of the window is compared
     with it (check.py); its time is not in setup_s;
  5. the last line of standard output is the result object.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import resource
import sys
import time

from . import cells, check, window
from .meter import CompileMeter, GcMeter
from .trace import TraceSummary, Tracer

OUT_DIR = ".bench_out"      # under the checkout; .gitignore lists it


@dataclasses.dataclass
class RunContext:
    cell: cells.Cell
    device: dict
    records: list
    t_open: float
    t_close: float
    setup_s: float
    meter_setup: dict
    meter_window: dict
    summary: TraceSummary | None = None


def note(obj) -> None:
    """An earlier line of standard output: facts, never the result."""
    print(json.dumps(obj, default=plain), flush=True)


def plain(x):
    return x.tolist() if hasattr(x, "tolist") else str(x)


def _finite(x):
    """json has no Infinity: a reading that could not be taken prints as
    1e300, far over any limit."""
    return x if math.isfinite(x) else 1e300


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, t_process_start: float, sut=None,
             require_chip: bool = True) -> int:
    """Runs the cell and prints its lines; returns the exit code. `sut` is
    the system-under-test module (harness/sut.py unless a test hands in one
    with the timed path broken underneath)."""
    cell = cells.load_cell(root, cell_name)
    if sut is None:
        from . import sut
    device = sut.device_facts()
    if require_chip:
        refusal = sut.chip_refusal(device, cell.chips)
        if refusal:
            print(f"refused: {refusal}", file=sys.stderr, flush=True)
            return 2
    cache_dir = sut.enable_cache()
    note({"phase": "device", "device": device, "cache_dir": cache_dir,
          "cell": cell.name, "seed": seed,
          "since_start_s": time.perf_counter() - t_process_start})
    meter = CompileMeter()

    config, traffic = cell.config, cell.traffic
    data = cells.plugin(root, "datagen", config["datagen"]).generate(
        config, seed)
    t0 = time.perf_counter()
    system = sut.System(config, data, seed, cells.plugin(
        root, "queries", config["query"]).query_kwargs(config, data))
    note({"phase": "cluster", "seconds": time.perf_counter() - t0,
          "since_start_s": time.perf_counter() - t_process_start,
          "memory": sut.memory_stats()})
    n_warm = int(traffic["warmup_surveys"])
    warm = []
    for i in range(n_warm):
        rec = window.one_survey(system, sut, seed, i)
        warm.append(rec)
        note({"phase": "warmup", "survey": i, "seconds": rec.seconds,
              "ok": rec.outputs is not None, "memory": sut.memory_stats()})
    # Tracing leaves millions of objects behind. The full collection that
    # walks them is made here, in set-up, as any other warming up; nothing
    # is frozen or switched off, so the window's collector is a user's.
    t0 = time.perf_counter()
    gc.collect()
    note({"phase": "collect", "seconds": time.perf_counter() - t0})
    gc_meter = GcMeter()
    tracer = None
    if trace:
        tracer = Tracer(os.path.join(root, OUT_DIR, "trace", cell.name))
        tracer.start()
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - t_process_start
    meter_setup = meter.between(float("-inf"), t_setup_end)
    # the process's own CPU seconds and page faults beside the wall: set-up
    # swings with how the process is started (PERF.md, Open questions), and
    # these say whether it computed, waited, or fought the allocator
    used = resource.getrusage(resource.RUSAGE_SELF)
    note({"phase": "setup", "setup_s": setup_s, **meter_setup,
          "cpu_user_s": used.ru_utime, "cpu_sys_s": used.ru_stime,
          "minor_faults": used.ru_minflt})

    records, t_open, t_close = window.run_window(
        system, sut, seed, seconds, first_index=n_warm,
        max_surveys=int(config["traced_surveys"]) if trace else None,
        annotate=tracer.annotate if tracer else None)
    meter_window = meter.between(t_open, float("inf"))
    gc_window = gc_meter.close()
    memory = sut.memory_stats()
    peak = sut.memory_peak(memory)
    oracle = sut.host_oracle_calls()
    note({"phase": "window", "surveys": len(records),
          "window_s": t_close - t_open, "memory_peak_bytes": peak,
          "memory": memory,
          "survey_s": [r.seconds for r in records][:64],
          "survey_s_p95": sorted(r.seconds for r in records)[
              math.ceil(0.95 * len(records)) - 1],
          "phase_s_mean": _phase_means(records), **meter_window,
          **gc_window})

    ctx = RunContext(cell, device, records, t_open, t_close, setup_s,
                     meter_setup, meter_window)
    device_line = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if tracer:
        t0 = time.perf_counter()
        tracer.stop()
        t1 = time.perf_counter()
        ctx.summary = TraceSummary(tracer.load())
        note({"phase": "trace", "stop_s": t1 - t0,
              "read_s": time.perf_counter() - t1,
              "device_events": sum(len(ev) for ev
                                   in ctx.summary.planes.values())})
        device_line.update(busy_s=ctx.summary.busy_s,
                           window_s=ctx.summary.window_s)
        breakdown = {
            "device_ops": ctx.summary.device_ops(10),
            "idle_gaps": ctx.summary.idle_gaps(
                _spans_in_trace_clock(records, tracer, ctx.summary), 10)}

    # the program's state goes before the reference runs
    del system
    gc.collect()

    t0 = time.perf_counter()
    reference = cells.plugin(root, "reference", config["reference"])
    expected = reference.expect(config, data)
    compared = check.compare_window(config, reference, expected,
                                    warm + records, oracle)
    for c in compared.values():
        c["value"] = _finite(c["value"])
    note({"phase": "reference", "seconds": time.perf_counter() - t0,
          "surveys_compared": len(warm) + len(records)})

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = cells.plugin(root, "readers", m["spec"]["reader"])
        value = reader.read(m["spec"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for r in records if r.outputs is None)
    line = {"correct": check.verdict(compared), "attempted": len(records),
            "failed": failed, "metrics": metrics, "device": device_line}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line, default=plain), flush=True)
    return 0


def _phase_means(records) -> dict:
    names = sorted({n for r in records for n in r.phases})
    return {n: sum(r.phases.get(n, 0.0) for r in records) / len(records)
            for n in names}


def _spans_in_trace_clock(records, tracer: Tracer, summary) -> list:
    """The traced surveys' PhaseTimers spans, moved from perf_counter's
    clock to the trace's by the offset taken at each survey's mark."""
    starts = {m[0]: m[1] for m in summary.marks}
    out = []
    for rec in records:
        mark_ns = starts.get(f"bench:survey:{rec.index}")
        if mark_ns is None:
            continue
        t_mark = tracer.offsets[rec.index]
        out.extend((name, mark_ns + int((a - t_mark) * 1e9),
                    mark_ns + int((b - t_mark) * 1e9))
                   for name, a, b in rec.spans)
    return out
