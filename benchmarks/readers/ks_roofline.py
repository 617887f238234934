"""The key switch's share of its HBM roofline: the bytes its G1 work has to
move (`harness/work_ks.py`) over the chip's HBM bandwidth, as a share of
the device seconds of the ops matching `kernel_pattern` inside the
program's span `phase` (the phase moved to the trace's clock as
`phase_device` moves it), per traced survey, averaged over the device
planes. With no trace, no such span or no such op inside it there is
nothing to read.

`kernel_seconds` is the sum `readers/dro_roofline.py` and
`readers/obf_roofline.py` each write out inside their `read`: a PR that adds
a cell may not edit those files, so it stands here a third time, as a
function the next `benchmark` issue can point all three at.
"""
import re

from benchmarks.harness import cells, work, work_ks
from benchmarks.harness.trace import _union
from benchmarks.readers.phase_device import phase_spans_ns


def kernel_seconds(ctx, spans, kernel_pattern: str) -> float:
    """Device seconds of the ops matching the pattern inside `spans`
    (nanosecond intervals on the trace's clock), averaged over planes."""
    kernel = re.compile(kernel_pattern)
    busy_ns = 0
    for ops in ctx.summary.planes.values():
        for lo, hi in spans:
            busy_ns += sum(b - a for a, b in _union(
                (max(a, lo), min(b, hi)) for name, a, b in ops
                if a < hi and b > lo and kernel.search(name)))
    return busy_ns / 1e9 / ctx.summary.n_planes


def read(spec, ctx):
    if ctx.summary is None:
        return None
    spans = phase_spans_ns(ctx, spec["phase"])
    if not spans:
        return None
    seconds = kernel_seconds(ctx, spans, spec["kernel_pattern"])
    if seconds <= 0:
        return None
    config = ctx.cell.config
    v = cells.plugin(ctx.cell.root, "queries",
                     config["query"]).n_values(config)
    least = work_ks.ks_bytes_per_survey(config, v) * len(spans) \
        / work.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
