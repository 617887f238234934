"""The program's own process tracer (`drynx_tpu.utils.timers.PROCESS`): jax's
trace / lower / compile events as spans `jax/<kind>:<function>` on the host
clock, and the program's counters (`h2d_bytes`, `d2h_bytes`, `surveys`, ...).

This reader imports the program. The harness keeps to `sut.py` and
`queries/` for that; a reader of the program's tracer has to read it where
it lives. A program that has no such tracer (a commit before PR 26) gives
None for every metric here, as does a span or counter that is not there; a
program that does not import is a fault, and raises.

`reduce`:
  longest_s         seconds of the longest span among `names`
  count             how many spans of `names`, or with `prefix`; in set-up
                    the tracer folds an event under a millisecond into a
                    count by name (`folded()`), and those count too
  count_per_survey  that count over the surveys of the window
  counter_ratio     sum of the counters `counters` over the counter `per`
`during` picks the spans by when they ENDED: `setup` is before the window
opened, `window` is inside it.
"""


def tracer():
    from drynx_tpu.utils import timers

    found = getattr(timers, "PROCESS", None)
    return found if hasattr(found, "counters") else None


def _spans(spec, ctx, process) -> list:
    if "names" in spec:
        found = [r for r in process.records("jax/")
                 if r.name in spec["names"]]
    else:
        found = process.records(spec["prefix"])
    if spec["during"] == "setup":
        return [r for r in found if r.t1 <= ctx.t_open]
    return [r for r in found if ctx.t_open <= r.t1 <= ctx.t_close]


def read(spec, ctx):
    process = tracer()
    if process is None:
        return None
    reduce = spec["reduce"]
    if reduce == "counter_ratio":
        counters = process.counters()
        if not counters.get(spec["per"]):
            return None
        return sum(counters.get(k, 0) for k in spec["counters"]) \
            / counters[spec["per"]]
    spans = _spans(spec, ctx, process)
    if reduce == "longest_s":
        return max((r.t1 - r.t0 for r in spans), default=None)
    if reduce == "count":
        folded = process.folded() if spec["during"] == "setup" else {}
        return len(spans) + sum(
            n for name, (n, _) in folded.items()
            if name in spec.get("names", ()) or "names" not in spec
            and name.startswith(spec["prefix"]))
    if reduce == "count_per_survey":
        return len(spans) / len(ctx.records) if ctx.records else None
    raise ValueError(f"process_spans reader: unknown reduce {reduce!r}")
