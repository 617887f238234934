"""The program's spans on the profiler's own clock, beside the harness's.

    python3 benchmarks/check_annotations.py --workload <cell>
    python3 benchmarks/check_annotations.py --workload <cell> --seed 1 --seconds 12 --run

The program opens a `jax.profiler.TraceAnnotation("drynx:<path>")` with every
phase and step (`drynx_tpu/utils/timers.py`), so under a profiler session
its spans lie in the host plane of the same trace as the device's ops. The
benchmark does not read them yet: it moves the PhaseTimers spans to the
trace's clock by one `perf_counter` offset a survey (`Tracer.offsets`).
This script shows that the two agree.

Without `--run` it reads the trace the cell's last `--trace 1` run left
(where `Tracer.load` finds it) and lists the `drynx:` annotations by name,
with their count and seconds, and the plane and line they were found on.
With `--run` it first makes a traced run of its own in this process (one
warm-up survey, then the configuration's `traced_surveys` under the
profiler, through the window's own call), and prints for each phase of
each traced survey the difference between the annotation's interval and
the same span moved by the harness's offset. One JSON line each; no result
line: this is not the benchmark's command.
"""
import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "drynx:"


def find_trace(out_dir: str) -> str:
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise SystemExit(f"no trace under {out_dir}: make a --trace 1 run "
                         "of the cell first, or give --run")
    return max(found, key=os.path.getmtime)


def host_annotations(path: str) -> tuple:
    """([name, start_ns, dur_ns] of the program's annotations, name without
    its prefix; the (plane, line) pairs they were found on)."""
    import jax.profiler as jp

    found, where = [], set()
    for plane in jp.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    name = e.name[len(PREFIX):].split("#", 1)[0]
                    found.append([name, int(e.start_ns), int(e.duration_ns)])
                    where.add((plane.name, line.name))
    return sorted(found, key=lambda a: a[1]), sorted(where)


def by_name(annotations) -> dict:
    out: dict = {}
    for name, _, dur in annotations:
        row = out.setdefault(name, {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] += dur / 1e9
    return out


def against_offsets(annotations, moved_spans) -> list:
    """For each span the harness moved to the trace's clock (name,
    start_ns, end_ns), the annotation of that name that starts nearest to
    it, and how far apart the two intervals' starts and ends lie."""
    out = []
    for name, s0, s1 in moved_spans:
        same = [a for a in annotations if a[0] == name]
        if not same:
            out.append({"name": name, "annotation": None})
            continue
        _, a0, dur = min(same, key=lambda a: abs(a[1] - s0))
        out.append({"name": name, "annotation": [a0, a0 + dur],
                    "moved": [s0, s1],
                    "start_diff_ms": (a0 - s0) / 1e6,
                    "end_diff_ms": (a0 + dur - s1) / 1e6})
    return out


def traced_run(cell, seed: int, seconds: float, out_dir: str) -> list:
    """One warm-up survey, then the traced window; returns the surveys'
    spans as the harness moves them to the trace's clock."""
    from benchmarks.harness import cells, runner, sut, window
    from benchmarks.harness.trace import Tracer, TraceSummary

    config = cell.config
    data = cells.plugin(cell.root, "datagen", config["datagen"]).generate(
        config, seed)
    system = sut.System(config, data, seed, cells.plugin(
        cell.root, "queries", config["query"]).query_kwargs(config, data))
    window.one_survey(system, sut, seed, 0)
    tracer = Tracer(out_dir)
    tracer.start()
    records, _, _ = window.run_window(
        system, sut, seed, seconds, first_index=1,
        max_surveys=int(config["traced_surveys"]), annotate=tracer.annotate)
    tracer.stop()
    return runner._spans_in_trace_clock(records, tracer,
                                        TraceSummary(tracer.load()))


if __name__ == "__main__":      # at module level: see run.py on frames
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--run", action="store_true",
                    help="make a traced run here and compare with the "
                         "harness's offsets")
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.harness import cells, runner, sut

    cell = cells.load_cell(ROOT, args.workload)
    out_dir = os.path.join(ROOT, runner.OUT_DIR, "trace", cell.name)
    moved = None
    if args.run:
        refusal = sut.chip_refusal(sut.device_facts(), cell.chips)
        if refusal:
            print(f"refused: {refusal}", file=sys.stderr)
            sys.exit(2)
        sut.enable_cache()
        moved = traced_run(cell, args.seed, args.seconds, out_dir)
    path = find_trace(out_dir)
    annotations, where = host_annotations(path)
    print(json.dumps({"trace": os.path.relpath(path, ROOT),
                      "found_on": where, "annotations": len(annotations)}))
    for name, row in sorted(by_name(annotations).items()):
        print(json.dumps({"annotation": name, **row}))
    if moved is not None:
        rows = against_offsets(annotations, moved)
        for row in rows:
            print(json.dumps(row))
        phases = [r for r in rows if "/" not in r["name"]]
        worst = max((max(abs(r["start_diff_ms"]), abs(r["end_diff_ms"]))
                     for r in phases if r["annotation"]), default=None)
        print(json.dumps({"phases_compared": len(phases),
                          "missing": [r["name"] for r in rows
                                      if not r["annotation"]],
                          "worst_phase_diff_ms": worst}))
