"""Readers, one module per `reader` a metric file names. Each has
`read(spec, ctx) -> number or None`. `ctx` is the run's RunContext
(harness/runner.py). A reader that finds nothing to read returns None and
the metric is left out of the line; it never makes up a 0."""
