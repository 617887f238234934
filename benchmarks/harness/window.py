"""The measured window: the one general load generator.

It reads a traffic mix (`traffic/<name>.json`) and drives the system with
it. The one shape of load it knows: a closed loop of one client, one
survey in flight, back to back until the window's seconds have passed (the
survey in flight at that moment finishes and counts); every survey is a
fresh query with the randomness `seed_i = (run seed * SEED_STRIDE + i) mod
2**31`. What a mix says today is how many surveys warm the cell up
(`warmup_surveys`); another shape of load (clients, arrivals) is a new key
here when a mix first needs it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
import traceback

MAX_FAILURES = 3
SEED_STRIDE = 1000003


@dataclasses.dataclass
class SurveyRecord:
    index: int
    seed: int
    t_submit: float
    t_done: float
    outputs: dict | None        # None: the survey raised
    phases: dict
    spans: list

    @property
    def seconds(self) -> float:
        return self.t_done - self.t_submit


def survey_seed(run_seed: int, index: int) -> int:
    return (run_seed * SEED_STRIDE + index) % (2 ** 31)


def one_survey(system, sut, run_seed: int, index: int,
               annotate=None) -> SurveyRecord:
    """Build a fresh query outside the timed span, then time run_survey
    from call to returned, decoded, host-side result."""
    seed = survey_seed(run_seed, index)
    query = system.new_query()
    mark = annotate(index) if annotate else contextlib.nullcontext()
    outputs, phases, spans = None, {}, []
    with mark:
        t0 = time.perf_counter()
        try:
            result = system.run(query, seed)
            outputs = sut.outputs_of(result, system.roster)
            phases, spans = sut.phase_seconds(result), sut.phase_spans(result)
        except Exception:               # counted as failed, never hidden
            traceback.print_exc(file=sys.stderr)
        t1 = time.perf_counter()
    return SurveyRecord(index, seed, t0, t1, outputs, phases, spans)


def run_window(system, sut, run_seed: int, seconds: float,
               first_index: int, max_surveys: int | None = None,
               annotate=None):
    """Surveys back to back for `seconds`, or `max_surveys` if that comes
    first (a traced run). Returns (records, t_open, t_close)."""
    records, failures = [], 0
    t_open = time.perf_counter()
    while True:
        rec = one_survey(system, sut, run_seed, first_index + len(records),
                         annotate)
        records.append(rec)
        failures += rec.outputs is None
        if rec.t_done - t_open >= seconds or failures >= MAX_FAILURES:
            break
        if max_surveys is not None and len(records) >= max_surveys:
            break
    return records, t_open, records[-1].t_done
