"""What the device did inside one of the program's phases, beside the G1
kernels: seconds per traced survey of

    union(device-op intervals inside the phase)
      - union(intervals of the ops matching `kernel_pattern` inside it)

The ops are the trace summary's (`harness/trace.py`: clipped to the traced
window, on the trace's clock). A phase's span is the program's PhaseTimers
span called `phase`, moved to that clock by its survey's mark: the mark's
start + (t - the record's t_submit). Unions, because a `while` op holds its
body's ops: they are counted once. Averaged over the device planes, as
`busy_s` is. A phase that a traced survey has, with no glue op in it, reads
0.0; with no trace, no mark or no such phase there is nothing to read.
"""
import re

from benchmarks.harness.trace import MARK, _union


def _seconds(intervals) -> float:
    return sum(b - a for a, b in _union(intervals)) / 1e9


def phase_spans_ns(ctx, phase: str) -> list:
    """(start_ns, end_ns) of `phase` in every traced survey."""
    starts = {m[0]: m[1] for m in ctx.summary.marks}
    out = []
    for rec in ctx.records:
        mark_ns = starts.get(f"{MARK}{rec.index}")
        if mark_ns is None:
            continue
        out.extend((mark_ns + int((a - rec.t_submit) * 1e9),
                    mark_ns + int((b - rec.t_submit) * 1e9))
                   for name, a, b in rec.spans if name == phase)
    return out


def read(spec, ctx):
    if ctx.summary is None:
        return None
    spans = phase_spans_ns(ctx, spec["phase"])
    if not spans:
        return None
    kernel = re.compile(spec["kernel_pattern"])
    glue = 0.0
    for ops in ctx.summary.planes.values():
        for lo, hi in spans:
            inside = [(name, max(a, lo), min(b, hi)) for name, a, b in ops
                      if a < hi and b > lo]
            glue += _seconds((a, b) for _, a, b in inside) \
                - _seconds((a, b) for name, a, b in inside
                           if kernel.search(name))
    return glue / ctx.summary.n_planes / len(spans)
