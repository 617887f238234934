"""The device trace of a traced run (harness/trace.py TraceSummary).

`reduce`:
  seconds_per_survey  device seconds of the ops matching `pattern`, over
                      the surveys traced
  idle_pct            100 * (1 - busy / traced window)
  hbm_roofline_pct    bytes the matching ops have to move per survey
                      (harness/work.py) over the chip's HBM bandwidth, as a
                      share of their device time
Without a trace, or where nothing matches, there is nothing to read.
"""
from benchmarks.harness import cells, work


def read(spec, ctx):
    summary = ctx.summary
    if summary is None:
        return None
    reduce = spec["reduce"]
    if reduce == "idle_pct":
        return 100.0 * (1.0 - summary.busy_s / summary.window_s)
    seconds = summary.seconds_matching(spec["pattern"])
    if seconds <= 0:
        return None
    surveys = len(summary.marks)
    if reduce == "seconds_per_survey":
        return seconds / surveys
    if reduce == "hbm_roofline_pct":
        config = ctx.cell.config
        v = cells.plugin(ctx.cell.root, "queries",
                         config["query"]).n_values(config)
        least = work.g1_bytes_per_survey(config, v) * surveys \
            / work.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
        return 100.0 * least / seconds
    raise ValueError(f"trace reader: unknown reduce {reduce!r}")
