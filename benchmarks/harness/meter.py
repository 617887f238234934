"""Meters on the harness's own process: what jax traced, lowered and
compiled, and when; and when Python's garbage collector ran.

A copy of chip_smoke.py's CompileMeter (PR 21) that keeps every event as an
interval on the host clock. Trace events nest (an inner jit is traced inside
its caller's trace), so seconds of tracing and lowering are the length of
the UNION of those intervals, not their sum. Compile events do not nest;
`requests` counts backend compile requests, persistent-cache hits included.
"""
from __future__ import annotations

import gc
import sys
import time

EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ECHO_OVER_S = 1.0       # events this long go to standard error as they end


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


class CompileMeter:
    def __init__(self):
        from jax import monitoring

        self.events: list = []      # (kind, t0, t1, name)
        self.hits: list = []        # times of persistent-cache hits
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        kind = EVENTS.get(event)
        if kind is None:
            return
        now = time.perf_counter()
        name = kw.get("fun_name", "?")
        self.events.append((kind, now - seconds, now, name))
        if seconds >= ECHO_OVER_S:
            print(f"[{kind}] {name}: {seconds:.1f}s", file=sys.stderr,
                  flush=True)

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> dict:
        """Totals of the events that ENDED in [t0, t1)."""
        inside = [e for e in self.events if t0 <= e[2] < t1]
        return {
            "trace_lower_s": union_seconds(
                (a, b) for k, a, b, _ in inside if k != "compile"),
            "compile_s": sum(b - a for k, a, b, _ in inside
                             if k == "compile"),
            "requests": sum(1 for k, *_ in inside if k == "compile"),
            "cache_hits": sum(1 for t in self.hits if t0 <= t < t1),
        }


class GcMeter:
    """Runs of Python's garbage collector from now until `close()`. Tracing
    leaves millions of objects behind, and a full collection that walks
    them takes a second or more: where a survey of the window reads long,
    the `window` line says whether the collector stood in it."""

    def __init__(self):
        self.runs = self.full = 0
        self.seconds = self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.runs += 1
            self.full += info["generation"] == 2
            self.seconds += time.perf_counter() - self._t0

    def close(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        return {"gc_runs": self.runs, "gc_full": self.full,
                "gc_s": self.seconds}
