"""The obfuscation phase's share of its HBM roofline: the bytes its G1 work
has to move (`harness/work_obf.py`) over the chip's HBM bandwidth, as a
share of the device seconds of the ops matching `kernel_pattern` inside the
program's span `phase` (the phase moved to the trace's clock as
`phase_device` moves it), per traced survey, averaged over the device
planes. With no trace, a configuration that does not obfuscate, no such
span or no such op inside it there is nothing to read.
"""
import re

from benchmarks.harness import cells, work, work_obf
from benchmarks.harness.trace import _union
from benchmarks.readers.phase_device import phase_spans_ns


def read(spec, ctx):
    config = ctx.cell.config
    if ctx.summary is None or not config.get("obfuscation"):
        return None
    spans = phase_spans_ns(ctx, spec["phase"])
    if not spans:
        return None
    kernel = re.compile(spec["kernel_pattern"])
    busy_ns = 0
    for ops in ctx.summary.planes.values():
        for lo, hi in spans:
            busy_ns += sum(b - a for a, b in _union(
                (max(a, lo), min(b, hi)) for name, a, b in ops
                if a < hi and b > lo and kernel.search(name)))
    seconds = busy_ns / 1e9 / ctx.summary.n_planes
    if seconds <= 0:
        return None
    v = cells.plugin(ctx.cell.root, "queries",
                     config["query"]).n_values(config)
    least = work_obf.obf_bytes_per_survey(config, v) * len(spans) \
        / work.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
