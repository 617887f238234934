"""The program's PhaseTimers, read from each survey's result: mean seconds
per survey of the phase `key`, over the surveys of the window that have
it."""


def read(spec, ctx):
    seen = [r.phases[spec["key"]] for r in ctx.records
            if spec["key"] in r.phases]
    if not seen:
        return None
    return sum(seen) / len(seen)
