"""jax.monitoring, through the harness's CompileMeter: `key` is one of
trace_lower_s, compile_s, requests, cache_hits; `during` is setup or
window; `per_survey` divides by the surveys of the window."""


def read(spec, ctx):
    totals = ctx.meter_setup if spec["during"] == "setup" else ctx.meter_window
    if totals is None or spec["key"] not in totals:
        return None
    value = totals[spec["key"]]
    if spec.get("per_survey"):
        if not ctx.records:
            return None
        value = value / len(ctx.records)
    return value
