"""The program's one tracer: phase timers -> CSV, and spans from process
start to the decoded result.

Mirrors unlynx StartTimer/EndTimer keyed by "<serverID>_<Phase>" (used at
reference services/service.go:381,412,717-744 and across lib/proof), whose
CSV output feeds simul/test_data/parse_time_data_test.go. The phase taxonomy
(SURVEY.md §5) is preserved so benchmark output stays comparable:
DataCollectionProtocol, AggregationPhase, KeySwitchingPhase, DPencoding,
VerifyRange, VerifyAggregation, VerifyKeySwitch, GradientDescent, Decryption,
AllProofs, JustExecution.

Beside the accumulated phase seconds every `PhaseTimers` keeps SPANS: a
phase (`start`/`end`), and a step inside it (`with step(...)`), named by
its path "<Phase>/<step>". A span records who caused it (the span open on
the same thread) and whose survey it belongs to; a phase, and every span of
the process tracer, also the thread's CPU seconds beside the wall: computed,
or waited. Each also opens a `jax.profiler.TraceAnnotation("drynx:<path>")`,
so that under any profiler session the program's spans lie in the same trace
as the device's ops, on the profiler's clock; with no session that is a
no-op check.

`PROCESS` is the tracer of what belongs to no survey: set-up spans, jax's
own trace / lower / compile events (`install_listener`), and counters.
"""
from __future__ import annotations

import collections
import contextlib
import io
import re
import sys
import threading
import time
from typing import NamedTuple, Optional

from ..resilience.policy import named_lock


class Span(NamedTuple):
    """One record. `cpu` is the thread's CPU seconds over the span
    (time.thread_time): taken for a phase and for every span of the process
    tracer (what the set-up report reads), None for a step of a survey
    (nothing reads it, and the clock is a system call) and where the
    caller owned the clock (`span`, jax's events);
    `parent` the name of the span open on the same thread when this one
    began; `survey` the id the tracer was made with."""
    name: str
    t0: float           # time.perf_counter
    t1: float
    cpu: Optional[float]
    parent: Optional[str]
    survey: Optional[str]


def _trace_annotation(path: str, survey: Optional[str]):
    """jax's host-span context manager, imported at the first span: the
    analysis pass and the lint tier import this package without jax."""
    from jax.profiler import TraceAnnotation

    if survey is None:
        return TraceAnnotation("drynx:" + path)
    return TraceAnnotation("drynx:" + path, survey=survey)


class _Open:
    """A span that has begun: one entry of a thread's stack. `in_view`: a
    phase, or a step under one (what `spans()` shows)."""
    __slots__ = ("path", "is_step", "parent", "in_view", "annotation",
                 "cpu0", "t0")

    def __init__(self, path: str, is_step: bool, above: Optional["_Open"],
                 survey: Optional[str], cpu: bool):
        self.path, self.is_step = path, is_step
        self.parent = above.path if above else None
        self.in_view = not is_step or (above is not None and above.in_view)
        self.annotation = _trace_annotation(path, survey)
        self.annotation.__enter__()
        self.cpu0 = time.thread_time() if cpu else None
        self.t0 = time.perf_counter()


class PhaseTimers:
    """Thread-safe named wall-clock timers accumulating per-phase seconds,
    and the spans under them."""

    step_cpu = False        # CPU seconds on steps too: the process tracer

    def __init__(self, survey: Optional[str] = None):
        self.survey = survey
        self._lock = named_lock("timers_lock")
        self._open: dict[str, _Open] = {}
        self._acc: dict[str, float] = {}
        self._spans: list[Span] = []
        self._view: set[str] = set()        # names that spans() shows
        self._local = threading.local()     # .stack: this thread's open spans

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _close(self, op: _Open, now: float) -> float:
        cpu = None if op.cpu0 is None else time.thread_time() - op.cpu0
        op.annotation.__exit__(None, None, None)
        dt = now - op.t0
        with self._lock:
            if not op.is_step:
                self._acc[op.path] = self._acc.get(op.path, 0.0) + dt
                if self._open.get(op.path) is op:   # closed by `_unwind`
                    del self._open[op.path]
            if op.in_view:
                self._view.add(op.path)
            self._spans.append(Span(op.path, op.t0, now, cpu, op.parent,
                                    self.survey))
        return dt

    def start(self, name: str) -> None:
        stack = self._stack()
        op = _Open(name, False, stack[-1] if stack else None, self.survey,
                   True)
        stack.append(op)
        with self._lock:
            self._open[name] = op

    def end(self, name: str) -> float:
        now = time.perf_counter()
        with self._lock:
            op = self._open.pop(name, None)
        if op is None:
            return 0.0
        return self._unwind(op, now)

    def _unwind(self, op: _Open, now: float) -> float:
        """Close `op`, and first what was left open under it on this
        thread (a phase begun inside a step that an exception cut)."""
        stack = self._stack()
        if op in stack:
            while stack[-1] is not op:
                self._close(stack.pop(), now)
            stack.pop()
        return self._close(op, now)

    @contextlib.contextmanager
    def step(self, name: str):
        """A step under the span open on this thread, as a context manager
        or a decorator. Steps are spans only: they never enter the
        accumulated phase seconds, so `items()` and `csv()` keep the phase
        taxonomy."""
        stack = self._stack()
        above = stack[-1] if stack else None
        op = _Open(f"{above.path}/{name}" if above else name, True, above,
                   self.survey, self.step_cpu)
        stack.append(op)
        try:
            yield
        finally:
            self._unwind(op, time.perf_counter())

    def add(self, name: str, dt: float) -> None:
        """Accumulate an externally-measured span. Unlike start/end this is
        safe under arbitrary thread overlap (no shared open-slot state) —
        it is how the concurrent proof creation/verification paths attribute
        their time (service.py: AllProofs / Verify<Type>)."""
        with self._lock:
            self._acc[name] = self._acc.get(name, 0.0) + dt

    def add_split(self, phase: str, kind: str, dt: float) -> None:
        """Attribute a span to the host_glue/device_compute split of a
        phase. Stored as "<phase>#<kind>" so the split rides every
        existing snapshot/CSV surface; split_summary() aggregates it."""
        self.add(f"{phase}#{kind}", dt)

    def split_summary(self) -> dict:
        """Aggregate the "<phase>#<kind>" split keys: per-phase seconds by
        kind plus the headline host_glue_s / device_compute_s /
        device_share numbers the device-path bench gates on."""
        with self._lock:
            items = list(self._acc.items())
        phases: dict[str, dict] = {}
        totals = {"host_glue": 0.0, "device_compute": 0.0}
        for k, v in items:
            if "#" not in k:
                continue
            phase, kind = k.rsplit("#", 1)
            phases.setdefault(phase, {})[kind] = round(v, 6)
            if kind in totals:
                totals[kind] += v
        denom = totals["host_glue"] + totals["device_compute"]
        return {"phases": phases,
                "host_glue_s": round(totals["host_glue"], 6),
                "device_compute_s": round(totals["device_compute"], 6),
                "device_share": (round(totals["device_compute"] / denom, 4)
                                 if denom > 0 else None)}

    def span(self, name: str, t0: float, t1: float,
             survey: Optional[str] = None) -> None:
        """Record an absolute (perf_counter) interval alongside its
        accumulated total. Unlike start/end the caller owns the clock, so
        overlapping spans from concurrent pipeline stages record correctly
        (the overlap proof in server/scheduler.py intersects these).
        `survey` names the survey a span of a shared tracer belongs to."""
        with self._lock:
            self._acc[name] = self._acc.get(name, 0.0) + (t1 - t0)
            self._view.add(name)
            self._spans.append(Span(name, t0, t1, None, None,
                                    survey or self.survey))

    def spans(self, prefix: str = "") -> list:
        """Absolute (name, t0, t1) of the phases and of the steps under a
        phase, ordered by start time (a parent before its steps). A span
        that lies outside every phase is left to `records()`."""
        with self._lock:
            out = [(s.name, s.t0, s.t1) for s in self._spans
                   if s.name.startswith(prefix) and s.name in self._view]
        return sorted(out, key=lambda s: (s[1], -s[2]))

    def records(self, prefix: str = "") -> list:
        """Every full `Span` record, ordered by start time."""
        with self._lock:
            out = [s for s in self._all() if s.name.startswith(prefix)]
        return sorted(out, key=lambda s: (s.t0, -s.t1))

    def _all(self):
        return self._spans

    def self_seconds(self, name: str) -> float:
        """Seconds of the spans called `name` that none of their children
        (the spans whose parent they are) cover."""
        with self._lock:
            own = [s for s in self._all() if s.name == name]
            kids = [s for s in self._all() if s.parent == name]
        total = 0.0
        for s in own:
            total += (s.t1 - s.t0) - union_seconds(
                (max(k.t0, s.t0), min(k.t1, s.t1)) for k in kids
                if k.t1 > s.t0 and k.t0 < s.t1)
        return total

    def clear(self) -> None:
        """Drop accumulated spans (benchmarks isolating a timed window)."""
        with self._lock:
            self._open.clear()
            self._acc.clear()
            self._spans.clear()
            self._view.clear()

    def __getitem__(self, name: str) -> float:
        return self._acc.get(name, 0.0)

    def items(self):
        return sorted(self._acc.items())

    def csv(self) -> str:
        """Two-row CSV (header + values), the simulation output format."""
        buf = io.StringIO()
        keys = [k for k, _ in self.items()]
        buf.write(",".join(keys) + "\n")
        buf.write(",".join(f"{self._acc[k]:.6f}" for k in keys) + "\n")
        return buf.getvalue()


def step_of(tm, name: str):
    """`tm.step(name)`, a step under the phase open on this thread, where
    the caller has a survey's timers; else a context that does nothing (a
    phase's function called outside a survey: a remote node, a script)."""
    return tm.step(name) if tm is not None else contextlib.nullcontext()


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


# jax.monitoring's duration events -> the kind in "jax/<kind>:<fun_name>"
JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
# the event jax records on every persistent-cache deserialization
# (jax/_src/compiler.py of the installed jax 0.9)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_HIT_SPAN = "jax/cache_hit"
FOLD_UNDER_S = 1e-3     # in set-up, a shorter jax event is folded, not kept
SETUP_MAX = 65536       # records at which set-up is sealed, survey or none
RING = 16384            # jax events kept once set-up is over: the newest
_WRAPPED = re.compile(r"^\w+\((.*)\)$")     # "jit(_fused_ks)" -> "_fused_ks"


def _fold(into: dict, name: str, n: int, seconds: float) -> None:
    row = into.setdefault(name, [0, 0.0])
    row[0] += n
    row[1] += seconds


class ProcessTracer(PhaseTimers):
    """`PhaseTimers` plus what only the process has: monotonic counters by
    name, and jax's trace / lower / compile events as spans.

    Set-up (until `seal_setup`: a cluster's first survey, or `SETUP_MAX`
    records) keeps every jax event of a millisecond or more as a span. The
    shorter ones, an inner jnp primitive's trace each and some hundred
    thousand under the grid's four programs, are FOLDED into [count,
    seconds] by name: for the process (`folded()`), and under the next kept
    trace that ends on the same thread, which is the function that held
    them (jax reports a trace as it ends, its caller's after it; the few
    that ran eagerly since the last kept trace ended are counted with it).
    After set-up: a ring of the newest jax events, whole. So neither a
    server that re-traces once a survey nor a process that only compiles
    grows without end."""

    step_cpu = True
    echo_over_s: Optional[float] = None     # jax events this long: to stderr

    def __init__(self):
        super().__init__()
        self._counts: dict[str, int] = {}
        self._ring: Optional[collections.deque] = None  # None: in set-up
        self._folded: dict[str, list] = {}      # name -> [count, seconds]
        self._pending: dict[int, dict] = {}     # thread -> fun -> the same
        self._held: dict[Span, dict] = {}       # kept trace -> fun -> same

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def counter(self, name: str) -> int:
        return self._counts.get(name, 0)

    def counters(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def folded(self) -> dict:
        """name -> (count, seconds) of set-up's jax events too short to be
        kept as spans."""
        with self._lock:
            return {k: tuple(v) for k, v in self._folded.items()}

    def _all(self):
        return self._spans if not self._ring else \
            self._spans + list(self._ring)

    def jax_event(self, name: str, seconds: float) -> None:
        """A span that jax reports as it ends: end = now, start = now minus
        its duration. Trace events nest (an inner jit is traced inside its
        caller); the intervals are kept so that self time can be had."""
        now = time.perf_counter()
        kind, _, fun = name.partition(":")
        with self._lock:
            self._acc[kind] = self._acc.get(kind, 0.0) + seconds
            if self._ring is not None:
                self._ring.append(Span(name, now - seconds, now, None, None,
                                       None))
                return
            thread = threading.get_ident()
            if seconds < FOLD_UNDER_S and fun:
                _fold(self._folded, name, 1, seconds)
                if kind == "jax/trace":
                    _fold(self._pending.setdefault(thread, {}), fun, 1,
                          seconds)
                return
            rec = Span(name, now - seconds, now, None, None, None)
            self._spans.append(rec)
            if kind == "jax/trace" and thread in self._pending:
                self._held[rec] = self._pending.pop(thread)
            if len(self._spans) >= SETUP_MAX:
                self._seal()

    def _seal(self) -> None:
        self._ring = collections.deque(maxlen=RING)
        self._pending.clear()

    def seal_setup(self) -> bool:
        """Set-up is over; true for the call that ended it."""
        with self._lock:
            if self._ring is not None:
                return False
            self._seal()
            return True

    def clear(self) -> None:
        super().clear()
        with self._lock:
            self._counts.clear()
            self._ring = None
            for d in (self._folded, self._pending, self._held):
                d.clear()

    # -- the set-up report ----------------------------------------------
    def setup_programs(self, survey_records=(), programs=()) -> list:
        """One row per top-level program of set-up (those named in
        `programs`, and whatever else traced and lowered for over a
        second): seconds of trace, lowering and compile, compiles and
        persistent-cache hits among them, the traces inside its own by
        function as [count, seconds] (nested, so they can sum over the
        program's), and the innermost span with CPU seconds, of
        `survey_records` or of this tracer, that holds the program's trace:
        its CPU over wall says whether the thread computed or waited."""
        with self._lock:
            spans = list(self._spans)
            held = {k: dict(v) for k, v in self._held.items()}
        jax_spans = sorted((s for s in spans if s.name.startswith("jax/")),
                           key=lambda s: (s.t0, -s.t1))
        holders = [s for s in list(survey_records) + spans
                   if s.cpu is not None]
        rows: dict[str, dict] = {}

        def row(fun: str) -> dict:
            return rows.setdefault(fun, {
                "program": fun, "trace_s": 0.0, "lower_s": 0.0,
                "compile_s": 0.0, "compiles": 0, "cache_hits": 0,
                "inner": {}, "within": None})

        open_traces: list = []
        compiles: list = []
        for s in jax_spans:
            kind, _, fun = s.name.partition(":")
            if kind == "jax/trace":
                # jax reports a trace as it ends, its caller's after it: a
                # span that ends before this one does not hold it
                while open_traces and open_traces[-1].t1 < s.t1:
                    open_traces.pop()
                if open_traces:
                    r = row(open_traces[0].name.partition(":")[2])
                    _fold(r["inner"], fun, 1, s.t1 - s.t0)
                else:
                    r = row(fun)
                    r["trace_s"] += s.t1 - s.t0
                    if r["within"] is None:
                        around = [h for h in holders
                                  if h.t0 <= s.t0 and s.t1 <= h.t1]
                        if around:
                            h = min(around, key=lambda h: h.t1 - h.t0)
                            r["within"] = (h.name, h.t1 - h.t0, h.cpu)
                for name, (n, sec) in held.get(s, {}).items():
                    _fold(r["inner"], name, n, sec)
                open_traces.append(s)
            elif kind == "jax/lower":
                row(fun)["lower_s"] += s.t1 - s.t0
            elif kind == "jax/compile":
                r = row(fun)
                r["compile_s"] += s.t1 - s.t0
                r["compiles"] += 1
                compiles.append((s, r))
            elif s.name == CACHE_HIT_SPAN:
                # a hit is recorded inside its compile request's span
                for c, r in compiles[::-1]:
                    if c.t0 <= s.t0 <= c.t1:
                        r["cache_hits"] += 1
                        break
        return [r for r in rows.values() if r["program"] in programs
                or r["trace_s"] + r["lower_s"] >= 1.0]

    def setup_report(self, survey_records=(), programs=()) -> str:
        rows = self.setup_programs(survey_records, programs)
        out = [f"set-up: trace {self['jax/trace']:.1f} s (nested, summed), "
               f"lower {self['jax/lower']:.1f} s, compile "
               f"{self['jax/compile']:.1f} s in "
               f"{self.counter('compile_requests')} requests, "
               f"{self.counter('cache_hits')} persistent-cache hits; "
               f"{len(self._spans)} spans kept, "
               f"{sum(n for n, _ in self.folded().values())} short jax "
               f"events folded",
               f"{'program':<24} {'trace_s':>8} {'lower_s':>8} "
               f"{'compile_s':>9}  cache  within (cpu / wall s); "
               f"longest inner traces"]
        if self.counter("exec_store_lookups"):
            # the executable store (utils/exec_store.py): a program that hit
            # it has no row below, since it neither traced nor lowered
            took = {kind: sum(s.t1 - s.t0 for s in self.records(
                f"setup/exec_store/{kind}:")) for kind in
                ("load", "compile", "save")}
            out.insert(1, (
                f"executable store: {self.counter('exec_store_hits')} of "
                f"{self.counter('exec_store_lookups')} look-ups hit, "
                f"{self.counter('exec_store_load_failures')} bad entries; "
                + ", ".join(f"{k} {v:.1f} s" for k, v in took.items())))
        for r in sorted(rows, key=lambda r: -(r["trace_s"] + r["lower_s"])):
            cache = ("-" if not r["compiles"] else "hit"
                     if r["cache_hits"] >= r["compiles"] else
                     f"{r['cache_hits']}/{r['compiles']}"
                     if r["cache_hits"] else "miss")
            within = "-"
            if r["within"]:
                name, wall, cpu = r["within"]
                within = f"{name} ({cpu:.1f} / {wall:.1f})"
            inner = ", ".join(f"{k} x{n} {sec:.1f}s" for k, (n, sec) in sorted(
                r["inner"].items(), key=lambda kv: -kv[1][1])[:6])
            out.append(f"{r['program']:<24} {r['trace_s']:>8.2f} "
                       f"{r['lower_s']:>8.2f} {r['compile_s']:>9.2f}  "
                       f"{cache:<5}  {within}; {inner or '-'}")
        return "\n".join(out)


# What `GLOBAL` was for: the one tracer of the process.
PROCESS = ProcessTracer()

_LISTENER_INSTALLED = False


def _on_duration(event: str, seconds: float, **kw) -> None:
    kind = JAX_EVENTS.get(event)
    if kind is None:
        return
    fun = str(kw.get("fun_name", "?"))
    m = _WRAPPED.match(fun)
    PROCESS.jax_event(f"jax/{kind}:{m.group(1) if m else fun}", seconds)
    if kind == "compile":
        PROCESS.count("compile_requests")
    over = PROCESS.echo_over_s
    if over is not None and seconds >= over:
        print(f"[{kind}] {fun}: {seconds:.1f}s", file=sys.stderr, flush=True)


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        PROCESS.jax_event(CACHE_HIT_SPAN, 0.0)
        PROCESS.count("cache_hits")


def install_listener() -> None:
    """The program's one jax.monitoring listener, on `PROCESS`. Idempotent.
    A jax without the monitoring API raises here; hit counts are what the
    chip smoke and the bench report as evidence that the cache works, so
    they are never silently zero."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True


__all__ = ["PhaseTimers", "ProcessTracer", "Span", "PROCESS",
           "install_listener", "step_of", "union_seconds"]
