"""Device trace: taking it, and the reduction from trace to numbers.

Taking it: `Tracer` wraps jax.profiler with the Python tracer off, marks
every traced survey with a `bench:survey:<i>` TraceAnnotation (a host span
on the profiler's own clock) and notes time.perf_counter() at each mark,
which gives the one offset between the program's PhaseTimers spans and the
trace.

The reduction works on a neutral form, so that it can be checked on a small
recorded trace (tests/data/): a dict
  {"devices": {plane name: [[name, start_ns, dur_ns], ...]},   device ops
   "marks": [[name, start_ns, dur_ns], ...]}                   host marks
`load_xplane` makes that form from the profiler's .xplane.pb.

  busy_s      union of the device-op intervals inside the traced window,
              averaged over the device planes
  window_s    first mark's start to last mark's end
  sums        seconds by op name (all planes)
  idle gaps   the longest stretches in which no op ran on a device, named
              by the phase span that covers the middle of the gap
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import time

MARK = "bench:survey:"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Tracer:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.offsets: dict = {}     # survey index -> perf_counter at its mark

    def start(self) -> None:
        import jax.profiler as jp

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        options = jp.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jp.start_trace(self.out_dir, profiler_options=options)

    def annotate(self, index: int):
        import jax.profiler as jp

        self.offsets[index] = time.perf_counter()
        return jp.TraceAnnotation(f"{MARK}{index}")

    def stop(self) -> None:
        import jax.profiler as jp

        jp.stop_trace()

    def load(self) -> dict:
        found = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no trace under "
                               + self.out_dir)
        return load_xplane(found[0])


_INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")


def short_name(event_name: str) -> str:
    """The trace names a device op by its whole HLO text (`%fusion.3 =
    u32[...] fusion(...), kind=...`). Kept: the instruction's name, and
    for a custom call its target, which is how a Mosaic kernel is told
    from the rest: `_scalar_mul_flat.1 tpu_custom_call`."""
    m = _INSTRUCTION.match(event_name)
    name = m.group(1) if m else event_name.split(" ", 1)[0]
    return name + " tpu_custom_call" if "tpu_custom_call" in event_name \
        else name


def op_family(name: str) -> str:
    """`_fixed_base_mul_flat.4 tpu_custom_call` -> `_fixed_base_mul_flat`:
    the instruction's name without its number and target."""
    return re.sub(r"\.\d+$", "", name.split(" ", 1)[0])


def load_xplane(path: str) -> dict:
    import jax.profiler as jp

    data = jp.ProfileData.from_file(path)
    devices, marks = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = list(plane.lines)
            chosen = [ln for ln in lines if ln.name == OPS_LINE]
            for line in chosen:
                devices.setdefault(plane.name, []).extend(
                    [short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events if e.name.startswith(MARK))
    marks.sort(key=lambda m: m[1])
    return {"devices": devices, "marks": marks}


def describe_xplane(path: str) -> list:
    """Planes, lines and event counts of a trace file: what a builder reads
    once by hand before trusting `load_xplane` on a new jax or chip."""
    import jax.profiler as jp

    out = []
    for plane in jp.ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(events),
                        "first": [e.name[:120] for e in events[:3]]})
    return out


def _union(intervals) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(events, lo: int, hi: int) -> list:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


class TraceSummary:
    """The numbers of one neutral-form trace, inside its marked window."""

    def __init__(self, neutral: dict):
        marks = neutral["marks"]
        if not marks or not any(neutral["devices"].values()):
            raise RuntimeError(
                "the trace holds no survey mark or no device op: "
                f"{len(marks)} marks, planes {sorted(neutral['devices'])}")
        self.marks = marks
        self.lo = min(m[1] for m in marks)
        self.hi = max(m[1] + m[2] for m in marks)
        self.window_s = (self.hi - self.lo) / 1e9
        self.planes = {p: _clip(ev, self.lo, self.hi)
                       for p, ev in sorted(neutral["devices"].items())}
        self.n_planes = len(self.planes)
        self._busy = {p: _union((a, b) for _, a, b in ev)
                      for p, ev in self.planes.items()}
        self.busy_s = sum(b - a for iv in self._busy.values()
                          for a, b in iv) / 1e9 / self.n_planes

    def seconds_matching(self, pattern: str) -> float:
        """Summed device seconds of the ops whose name matches, averaged
        over the device planes."""
        rx = re.compile(pattern)
        total = sum(b - a for ev in self.planes.values()
                    for name, a, b in ev if rx.search(name))
        return total / 1e9 / self.n_planes

    def device_ops(self, top: int = 10) -> list:
        """Device seconds by op family (all planes), heaviest first."""
        sums: dict = {}
        for ev in self.planes.values():
            for name, a, b in ev:
                fam = op_family(name)
                sums[fam] = sums.get(fam, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in
                sorted(sums.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, spans_ns, top: int = 10) -> list:
        """Idle time of the first device plane by what the host was doing:
        each stretch with no op running is named by the host span covering
        its middle (`spans_ns`: (name, start_ns, end_ns) on the trace's
        clock), else `between_phases` inside a survey mark and
        `between_surveys` outside; stretches of one name are summed."""
        busy = next(iter(self._busy.values()))
        edges = [self.lo] + [t for iv in busy for t in iv] + [self.hi]
        sums: dict = {}
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            name = next((n for n, s, e in spans_ns if s <= mid < e), None)
            if name is None:
                inside = any(m[1] <= mid < m[1] + m[2] for m in self.marks)
                name = "between_phases" if inside else "between_surveys"
            sums[name] = sums.get(name, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in
                sorted(sums.items(), key=lambda kv: -kv[1])[:top]]
