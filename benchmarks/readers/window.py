"""Wall time of a survey over the window: the window's elapsed time over
the surveys completed in it (not a mean of per-survey timers)."""


def read(spec, ctx):
    done = sum(1 for r in ctx.records if r.outputs is not None)
    if not done or spec["reduce"] != "mean":
        return None
    return (ctx.t_close - ctx.t_open) / done
