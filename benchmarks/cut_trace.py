"""Cut a profiler trace down to a small fixture in the neutral form.

    python3 -m benchmarks.cut_trace <trace.xplane.pb> <out.json> [events]

Keeps the first survey mark and `events` device ops that start inside it
(default 600), beginning a few ops before the first Mosaic kernel so that
the cut holds kernels, with the mark cut to begin and end where they do, so
that `TraceSummary` reads the cut as a whole window. Also prints the
trace's planes and lines: read them once by hand on a new jax or chip
before trusting `load_xplane`. This is how tests/data/trace_chip_*.json
were made; it needs jax but no chip (JAX_PLATFORMS=cpu).
"""
import json
import sys

from benchmarks.harness.trace import describe_xplane, load_xplane


def cut(neutral: dict, n_events: int) -> dict:
    name, start, dur = neutral["marks"][0]
    devices, end = {}, start
    begin = None
    for plane, events in neutral["devices"].items():
        inside = sorted((e for e in events if start <= e[1] < start + dur),
                        key=lambda e: e[1])
        first = next((i for i, e in enumerate(inside)
                      if "tpu_custom_call" in e[0]), 0)
        inside = inside[max(first - 20, 0):][:n_events]
        devices[plane] = inside
        if inside:
            begin = inside[0][1] if begin is None else min(begin,
                                                           inside[0][1])
            end = max([end] + [e[1] + e[2] for e in inside])
    begin = start if begin is None else begin
    return {"devices": devices, "marks": [[name, begin, end - begin]]}


def main(argv) -> int:
    path, out = argv[0], argv[1]
    n_events = int(argv[2]) if len(argv) > 2 else 600
    for row in describe_xplane(path):
        print(json.dumps(row))
    with open(out, "w") as f:
        json.dump(cut(load_xplane(path), n_events), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
