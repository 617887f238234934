"""Compile-cache observability: per-program trace/lower/compile timings.

The precompile driver (registry.py) is SERIAL by design, so per-program
rows are recorded in dispatch order and persistent-cache hits can be
attributed to the program whose .compile() triggered them. With the class
flag `echo` set (the precompile CLI, bench.py), every program prints to
stderr as it finishes — a killed cold-start run still shows where the wall
went.
"""
from __future__ import annotations

import sys

from ..resilience.policy import named_lock
from ..utils import timers


class CompileStats:
    """Thread-safe per-program AOT accounting + persistent-cache counters.

    Row statuses:
      compiled  — AOT traced+lowered+compiled (persistent cache fed)
      executed  — dispatched like runtime (dispatch caches warm; the
                  LocalCluster main-thread warmup mode)
      lowered   — traced+lowered only (--dry-run)
      skipped   — enumerated, but the current backend would not dispatch it
                  (e.g. host-oracle detours on CPU, Pallas-only ops)
      error     — trace/lower/compile raised
    """

    echo = False

    def __init__(self):
        self._lock = named_lock("compilestats_lock")
        self.rows: dict[str, dict] = {}
        self.persistent_hits = 0
        self.persistent_misses = 0

    @property
    def listener_hits(self) -> int:
        """Raw count of persistent-cache hits from the program's one
        jax.monitoring listener (utils/timers.py); the serial driver diffs
        it around each .compile() to classify hit/miss."""
        return timers.PROCESS.counter("cache_hits")

    def record(self, name: str, status: str, lower_s: float = 0.0,
               compile_s: float = 0.0, cache: str | None = None,
               detail: str = "") -> None:
        with self._lock:
            self.rows[name] = {"status": status, "lower_s": lower_s,
                               "compile_s": compile_s, "cache": cache,
                               "detail": detail}
            if cache == "hit":
                self.persistent_hits += 1
            elif cache == "miss":
                self.persistent_misses += 1
        if CompileStats.echo:
            extra = f" cache={cache}" if cache else ""
            extra += f" ({detail})" if detail else ""
            print(f"    [aot] {name}: {status} lower={lower_s:.3f}s "
                  f"compile={compile_s:.3f}s{extra}", file=sys.stderr,
                  flush=True)

    def count(self, status: str) -> int:
        with self._lock:
            return sum(1 for r in self.rows.values()
                       if r["status"] == status)

    def totals(self) -> dict:
        with self._lock:
            rows = list(self.rows.values())
        return {
            "programs": len(rows),
            "compiled": sum(1 for r in rows if r["status"] == "compiled"),
            "executed": sum(1 for r in rows if r["status"] == "executed"),
            "lowered": sum(1 for r in rows if r["status"] == "lowered"),
            "skipped": sum(1 for r in rows if r["status"] == "skipped"),
            "errors": sum(1 for r in rows if r["status"] == "error"),
            "lower_seconds": sum(r["lower_s"] for r in rows),
            "compile_seconds": sum(r["compile_s"] for r in rows),
            "persistent_hits": self.persistent_hits,
            "persistent_misses": self.persistent_misses,
        }

    def headline(self) -> dict:
        """Bonus keys for the bench headline JSON (bench.py)."""
        t = self.totals()
        return {
            "compile_cache_programs": t["programs"],
            "compile_cache_compiled": t["compiled"] + t["executed"],
            "compile_cache_skipped": t["skipped"],
            "compile_cache_trace_lower_seconds": round(
                t["lower_seconds"], 3),
            "compile_cache_compile_seconds": round(
                t["compile_seconds"], 3),
            "compile_cache_persistent_hits": t["persistent_hits"],
            "compile_cache_persistent_misses": t["persistent_misses"],
        }

    def table(self) -> str:
        """Human-readable per-program report (CLI output)."""
        with self._lock:
            rows = sorted(self.rows.items())
        if not rows:
            return "(no programs recorded)"
        w = max(len(n) for n, _ in rows)
        lines = [f"{'program':<{w}}  {'status':<9} {'lower_s':>8} "
                 f"{'compile_s':>9}  cache"]
        for n, r in rows:
            lines.append(
                f"{n:<{w}}  {r['status']:<9} {r['lower_s']:>8.3f} "
                f"{r['compile_s']:>9.3f}  {r['cache'] or '-'}")
        t = self.totals()
        lines.append(
            f"-- {t['programs']} programs: {t['compiled']} compiled, "
            f"{t['executed']} executed, "
            f"{t['lowered']} lowered, {t['skipped']} skipped, "
            f"{t['errors']} errors; lower {t['lower_seconds']:.1f}s, "
            f"compile {t['compile_seconds']:.1f}s, persistent cache "
            f"{t['persistent_hits']} hits / {t['persistent_misses']} misses")
        return "\n".join(lines)


# Process-global collector: LocalCluster warmup and the CLI both feed it,
# bench.py reads .headline() into the bonus JSON keys.
STATS = CompileStats()

# The event jax records on every persistent-cache deserialization.
CACHE_HIT_EVENT = timers.CACHE_HIT_EVENT


def install_cache_listener() -> None:
    """Count persistent-compilation-cache hits: installs the program's one
    jax.monitoring listener (utils/timers.install_listener; idempotent,
    raises on a jax without the monitoring API), which `listener_hits`
    reads."""
    timers.install_listener()
