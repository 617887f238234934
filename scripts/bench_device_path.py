#!/usr/bin/env python
"""Device-path bench: zero-copy wire decode x async shard dispatch — the
PR-14 headline numbers (BENCH_DEVPATH_r01).

One supervised child per variant (bench.py pattern: the parent is jax-free
and survives child segfaults/timeouts; each child writes a progressive
record the parent collects even from a corpse). Every child boots the SAME
in-process TCP roster — 3 CN / 8 DP / 3 VN, the net-plane roster, so the
persistent compile cache is shared — under a LinkModel charging real
per-frame latency+bandwidth, with an 8-way forced host mesh so the sharded
proof plane (dispatch_shards + put_shard prefetch) actually runs, and
executes the same three surveys:

  A  sum, proofs off, 3 timed reps        -> dispatch wall clock
  F  frequency_count, 3 timed reps        -> decode-heavy wall clock
  C  sum with proofs on, 2 timed reps     -> normalized VN transcript +
     the shard-pipeline wall (create/verify run through dispatch_shards)

Variants (env-driven, exactly the production kill-switches):

  host-serial     DRYNX_DEVICE_DECODE=off  DRYNX_ASYNC_DISPATCH=serial
  device-serial   decode on                DRYNX_ASYNC_DISPATCH=serial
  host-async      DRYNX_DEVICE_DECODE=off  async on
  device-async    decode on                async on        (headline)

A fifth "paired" child owns the wall bar: it alternates the full device
path (decode on + async) with the full host path (decode off + serial)
over interleaved proofs-on reps IN ONE PROCESS — cross-child wall
comparison on the shared 1-core box carries ~10% monotonic run-order
drift (r01 measured it: the four isolation children's walls order by
start time, not by variant), and interleaving cancels it.

The parent then checks the PR's acceptance bars: results and VN
transcripts byte-identical across all four isolation combinations,
every child reporting host_glue/device_compute split attribution, and
the paired child's device-path wall no worse than its host-path wall
(min-of-reps, WALL_TOL slack: on a single-core CPU box the widen does
identical memory work on either side of the "wire", so the bar is
"adds no measurable overhead" — on a real accelerator the widen leaves
the host entirely and the bar tightens).

Children run opt-level 0 + AVX2 + a persistent compile cache (the tier-1
test environment); the first child seeds the per-shard proof programs,
later children ride the cache.

Usage:
  python scripts/bench_device_path.py            # full -> BENCH_DEVPATH_r01.json
  python scripts/bench_device_path.py --smoke    # check.sh tier: one child,
                                                 # proofs-on survey, decode
                                                 # on/off transcript diff
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import bench  # noqa: E402  (jax-free supervisor helpers)

RECORD = os.path.join(ROOT, "BENCH_DEVPATH_r01.json")

ROLES = ["cn"] * 3 + ["dp"] * 8 + ["vn"] * 3
SMOKE_ROLES = ["cn", "cn", "dp", "dp", "dp", "vn", "vn"]
DATA_SEED = 77
DP_ROWS = 8
A_REPS = 3
F_REPS = 3
C_REPS = 2
PAIR_REPS = 3             # interleaved on/off proofs-on reps per mode
LINK_DELAY_MS = 50.0      # LAN-ish: keep link charges deterministic but
                          # small enough that decode/dispatch work shows
SMOKE_DELAY_MS = 25.0
CHILD_TIMEOUT_S = 3000.0  # first child compiles the per-shard proof
                          # programs cold; later children ride the cache
WALL_TOL = 0.02           # see module docstring: CPU-backend equal-work bar

VARIANTS = [
    ("host-serial",
     {"DRYNX_DEVICE_DECODE": "off", "DRYNX_ASYNC_DISPATCH": "serial"}),
    ("device-serial", {"DRYNX_ASYNC_DISPATCH": "serial"}),
    ("host-async", {"DRYNX_DEVICE_DECODE": "off"}),
    ("device-async", {}),
]


def log(msg):
    print(f"[device-path] {msg}", file=sys.stderr, flush=True)


def write_progressive(path, doc):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)


def variant_result(name, outcome, rc, elapsed_s, record):
    rec = dict(record or {})
    stage = rec.pop("stage", None)
    base = {"variant": name, "outcome": outcome, "rc": rc,
            "elapsed_s": round(elapsed_s, 1)}
    if outcome == "ok" and stage == "complete":
        base["status"] = "ok"
        base.update(rec)
        return base
    if outcome == "ok":
        base["status"] = "child_exited_without_record"
    elif outcome == "timeout":
        base["status"] = "timeout"
    elif outcome.startswith("signal:"):
        base["status"] = "killed_" + outcome.split(":", 1)[1].lower()
    else:
        base["status"] = "failed_" + outcome.replace(":", "")
    base["last_stage"] = stage or "none"
    base.update(rec)
    return base


def _arm_parent():
    def _bye(signum, frame):
        child = bench._CURRENT_CHILD
        if child is not None:
            try:
                child.kill()
            except OSError:
                pass
        os._exit(1)

    signal.signal(signal.SIGTERM, _bye)
    signal.signal(signal.SIGINT, _bye)


def _child_env(overrides, delay_ms):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        flags += " --xla_cpu_max_isa=AVX2"
    if "xla_backend_optimization_level" not in flags:
        flags += " --xla_backend_optimization_level=0"
    if "host_platform_device_count" not in flags:
        # the tier-1 mesh: 8 host devices so the proof plane shards and
        # dispatch_shards (enqueue/upload/block spans) actually runs
        flags += " --xla_force_host_platform_device_count=8"
    env["XLA_FLAGS"] = flags.strip()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    env["DRYNX_LINK_DELAY_MS"] = str(delay_ms)
    env["DRYNX_LINK_MBPS"] = "100.0"
    for k in ("DRYNX_DEVICE_DECODE", "DRYNX_DEVICE_DECODE_MIN",
              "DRYNX_ASYNC_DISPATCH", "DRYNX_POOL_MMAP",
              "DRYNX_FANOUT", "DRYNX_WIRE"):
        env.pop(k, None)
    env.update(overrides)
    return env


def _compare(by):
    """Acceptance comparisons over the per-variant records (full mode)."""
    cmp, accept = {}, {}

    iso = {n for n, _ in VARIANTS}

    def ok(name):
        return by.get(name, {}).get("status") == "ok"

    for key in ("a_result_sha", "f_result_sha"):
        shas = {n: r.get(key) for n, r in by.items()
                if n in iso and ok(n)}
        cmp[key + "s"] = shas
        accept.setdefault("results_identical", True)
        accept["results_identical"] &= \
            len(set(shas.values())) == 1 and bool(shas)
    tshas = {n: r.get("c_transcript_sha") for n, r in by.items()
             if n in iso and ok(n)}
    cmp["c_transcript_shas"] = tshas
    accept["transcripts_identical_all_four"] = \
        len(set(tshas.values())) == 1 and len(tshas) == len(VARIANTS)
    # split attribution present in every child (decode/upload glue always
    # records; the sharded C survey adds enqueue/block spans)
    attr = {n: r.get("split", {}) for n, r in by.items()
            if n in iso and ok(n)}
    accept["attribution_present"] = bool(attr) and all(
        a.get("host_glue_s", 0) > 0 and "WireDecode" in a.get("phases", {})
        for a in attr.values())
    # context only — cross-child walls carry run-order drift (docstring)
    cmp["c_wall_min_by_variant_s"] = {
        n: by[n].get("c_wall_min_s") for n in by if n in iso and ok(n)}
    # the acceptance wall bar: the paired child's interleaved reps
    if ok("paired"):
        p = by["paired"]
        cmp["paired_device_wall_s"] = p["pair_on_min_s"]
        cmp["paired_host_wall_s"] = p["pair_off_min_s"]
        cmp["device_path_strictly_faster"] = \
            p["pair_on_min_s"] <= p["pair_off_min_s"]
        accept["device_path_not_slower"] = \
            p["pair_on_min_s"] <= p["pair_off_min_s"] * (1.0 + WALL_TOL)
        accept["paired_transcripts_identical"] = \
            bool(p.get("pair_transcripts_equal"))
    return cmp, accept


def main_parent(args):
    _arm_parent()
    delay = args.delay_ms or (SMOKE_DELAY_MS if args.smoke
                              else LINK_DELAY_MS)
    timeout = args.timeout or (900 if args.smoke else CHILD_TIMEOUT_S)
    doc = {"round": "r01", "bench": "device_path", "smoke": bool(args.smoke),
           "roster": {r: (SMOKE_ROLES if args.smoke else ROLES).count(r)
                      for r in ("cn", "dp", "vn")},
           "link": {"delay_ms": delay, "mbps": 100.0},
           "wall_tolerance": WALL_TOL,
           "child_timeout_s": timeout, "variants": []}
    record_path = os.path.join(ROOT, ".device_path_record.json")
    out = args.out or RECORD

    plan = [("smoke", {})] if args.smoke else VARIANTS + [("paired", {})]
    for name, overrides in plan:
        try:
            os.remove(record_path)
        except OSError:
            pass
        env = _child_env(overrides, delay)
        cmd = [sys.executable, os.path.abspath(__file__), "--measure-child",
               "--variant", name, "--record-path", record_path]
        if args.smoke:
            cmd.append("--smoke")
        if name == "paired":
            cmd.append("--paired")
        log(f"{name}: starting child (timeout {timeout:.0f}s)")
        outcome, rc, elapsed, _out = bench.supervise_child(
            cmd, timeout, env=env)
        vt = variant_result(name, outcome, rc, elapsed,
                            bench.read_record(record_path))
        print(json.dumps(vt), flush=True)
        doc["variants"].append(vt)
        if not args.smoke or args.out:
            write_progressive(out, doc)
    try:
        os.remove(record_path)
    except OSError:
        pass

    bad = [v["variant"] for v in doc["variants"] if v["status"] != "ok"]
    if args.smoke:
        log(f"smoke done: {len(bad)} bad")
        return 1 if bad else 0
    by = {v["variant"]: v for v in doc["variants"]}
    cmp, accept = _compare(by)
    doc["comparisons"], doc["accept"] = cmp, accept
    write_progressive(out, doc)
    print(json.dumps({"comparisons": cmp, "accept": accept}), flush=True)
    failed = [k for k, v in accept.items() if not v]
    log(f"done: {len(doc['variants'])} variants, bad={bad}, "
        f"accept_failed={failed}")
    return 1 if bad or failed else 0


# ---------------------------------------------------------------------------
# Child (one variant; all jax work below)
# ---------------------------------------------------------------------------

_REC_PATH = None
_REC = {}


def wr(stage, **fields):
    _REC.update(fields)
    _REC["stage"] = stage
    if _REC_PATH is None:
        return
    tmp = _REC_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(_REC, f)
    os.replace(tmp, _REC_PATH)


def _plain(o):
    import numpy as np
    if isinstance(o, dict):
        return {str(k): _plain(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_plain(v) for v in o]
    if isinstance(o, np.ndarray):
        return o.tolist()
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    return o


def _sha(o):
    return hashlib.sha256(
        json.dumps(_plain(o), sort_keys=True).encode()).hexdigest()


def _boot(roles, tmpdir):
    import numpy as np
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.service.node import DrynxNode, RosterEntry

    rng = np.random.default_rng(DATA_SEED)
    nodes, entries = [], []
    for i, role in enumerate(roles):
        x, pub = eg.keygen(rng)
        data = None
        if role == "dp":
            data = rng.integers(0, 10, size=(DP_ROWS,)).astype(np.int64)
        n = DrynxNode(f"{role}{i}", x, pub, data=data,
                      db_path=os.path.join(tmpdir, f"{role}{i}.db"))
        n.start()
        entries.append(RosterEntry(name=f"{role}{i}", role=role,
                                   host=n.address[0], port=n.address[1],
                                   public=pub))
        nodes.append(n)
    return nodes, entries, rng


class _serial_dispatch:
    """Force one-at-a-time fan-out for warmups: the first trace of each
    kernel must not happen on concurrent server threads (XLA CPU client
    races on concurrent tracing — see tests/conftest.py history)."""

    def __enter__(self):
        self._prev = os.environ.get("DRYNX_FANOUT")
        os.environ["DRYNX_FANOUT"] = "serial"

    def __exit__(self, *exc):
        if self._prev is None:
            os.environ.pop("DRYNX_FANOUT", None)
        else:
            os.environ["DRYNX_FANOUT"] = self._prev


def _timer_delta(before, after):
    return {k: round(v - before.get(k, 0.0), 6)
            for k, v in after.items() if v - before.get(k, 0.0) > 0}


def _split_of(spans):
    """split_summary over a span-delta dict (same parse as PhaseTimers)."""
    from drynx_tpu.utils.timers import PhaseTimers

    t = PhaseTimers()
    for k, v in spans.items():
        t.add(k, v)
    return t.split_summary()


def main_child(args):
    global _REC_PATH
    _REC_PATH = args.record_path
    import tempfile

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import proof_plane as plane
    from drynx_tpu.resilience import policy as rp
    from drynx_tpu.service import transport as tp

    from drynx_tpu.service.node import RemoteClient, Roster

    roles = SMOKE_ROLES if args.smoke else ROLES
    tmpdir = tempfile.mkdtemp(prefix="device_path_")
    wr("boot", variant=args.variant, roles=roles,
       device_decode=tp.device_decode_on(), async_dispatch=plane.async_on(),
       n_shards=plane.n_shards(),
       link={"delay_ms": float(os.environ.get("DRYNX_LINK_DELAY_MS", 0)),
             "mbps": float(os.environ.get("DRYNX_LINK_MBPS", 0))})
    nodes, entries, rng = _boot(roles, tmpdir)
    roster = Roster(entries)
    client = RemoteClient(roster, rng)
    client.broadcast_roster()
    dl = eg.DecryptionTable(limit=1000)

    def run(op, sid, **kw):
        t0 = time.time()
        res = client.run_survey(op, query_min=0, query_max=9,
                                survey_id=sid, dlog=dl, **kw)
        return res, time.time() - t0

    def proofs_run(sid):
        t0 = time.time()
        res, block = client.run_survey(
            "sum", query_min=0, query_max=9, proofs=True, ranges=[(4, 4)],
            survey_id=sid, dlog=dl, timeout=rp.COLD_COMPILE_WAIT_S)
        norm = {k.replace(sid, "SID"): v for k, v in block["bitmap"].items()}
        return int(res), norm, time.time() - t0

    try:
        # -- warmup (forced serial fan-out: first kernel traces) ----------
        t0 = time.time()
        with _serial_dispatch():
            _, dt = run("frequency_count", "warm-f")
            wr("warm_f", warm_f_s=round(dt, 1))
            _, dt = run("sum", "warm-a")
            wr("warm_a", warm_a_s=round(dt, 1))
            _, _, dt = proofs_run("warm-c")
            wr("warm_c", warm_c_s=round(dt, 1),
               warmup_s=round(time.time() - t0, 1))

        if args.smoke:
            return _smoke_body(run, proofs_run)
        if args.paired:
            return _paired_body(proofs_run)

        base = plane.timers_snapshot()

        # -- survey A: proofs-off dispatch wall clock ---------------------
        walls, res = [], None
        for i in range(A_REPS):
            res, dt = run("sum", f"a{i}")
            walls.append(round(dt, 3))
        wr("survey_a", a_wall_s=walls, a_wall_min_s=min(walls),
           a_result_sha=_sha(int(res)))

        # -- survey F: tensor-heavy decode wall clock ---------------------
        walls, fres = [], None
        for i in range(F_REPS):
            fres, dt = run("frequency_count", f"f{i}")
            walls.append(round(dt, 3))
        wr("survey_f", f_wall_s=walls, f_wall_min_s=min(walls),
           f_result_sha=_sha(fres))

        # -- survey C: proofs on -> transcript + shard-pipeline wall ------
        walls, norm, cres = [], None, None
        for i in range(C_REPS):
            cres, norm, dt = proofs_run(f"bench-c{i}")
            walls.append(round(dt, 3))
        spans = _timer_delta(base, plane.timers_snapshot())
        wr("survey_c", c_wall_s=walls, c_wall_min_s=min(walls),
           c_result=cres, c_bitmap_len=len(norm),
           c_all_true=set(norm.values()) == {1},
           c_transcript_sha=_sha(norm))

        # -- attribution: measured-window spans, host/device split --------
        wr("complete", timers=spans, split=_split_of(spans))
        return 0
    finally:
        tp.set_conn_pool(None)
        for n in nodes:
            n.stop()


def _paired_body(proofs_run):
    """Interleaved device-path-on / host-path-off proofs-on reps in one
    process: the wall bar the parent gates on. Alternation cancels the
    monotonic run-order drift a cross-child comparison carries; min-of-
    reps cancels per-rep jitter. Both modes must also agree byte-for-
    byte on result and transcript."""
    _OFF = {"DRYNX_DEVICE_DECODE": "off", "DRYNX_ASYNC_DISPATCH": "serial"}

    def mode(sid, off):
        saved = {k: os.environ.get(k) for k in _OFF}
        if off:
            os.environ.update(_OFF)
        try:
            return proofs_run(sid)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # one off-mode warm rep: the device-mode kernels warmed in warmup
    mode("pair-warm-off", True)
    on_w, off_w, shas, results = [], [], set(), set()
    for i in range(PAIR_REPS):
        r, t, w = mode(f"pair-on{i}", False)
        on_w.append(round(w, 3))
        results.add(r)
        shas.add(_sha(t))
        r, t, w = mode(f"pair-off{i}", True)
        off_w.append(round(w, 3))
        results.add(r)
        shas.add(_sha(t))
    wr("complete", pair_on_wall_s=on_w, pair_off_wall_s=off_w,
       pair_on_min_s=min(on_w), pair_off_min_s=min(off_w),
       pair_transcripts_equal=len(shas) == 1 and len(results) == 1,
       pair_transcript_sha=shas.pop() if len(shas) == 1 else None)
    return 0


def _smoke_body(run, proofs_run):
    """One child, decode on/off x async/serial toggled in-process over the
    SAME proofs-on survey: results and normalized VN transcripts must be
    byte-identical, and the lazy decode must actually be live in the
    default-env legs (the asserts are the check.sh gate; walls are
    recorded, not asserted — the full bench owns the wall bar)."""
    from drynx_tpu.parallel import proof_plane as plane
    from drynx_tpu.service import transport as tp

    def variant(sid, **env):
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            return proofs_run(sid)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    assert tp.device_decode_on() and plane.async_on()   # default-on env
    r_on, t_on, w_on = variant("sm-on")
    r_off, t_off, w_off = variant("sm-off", DRYNX_DEVICE_DECODE="off")
    r_ser, t_ser, w_ser = variant("sm-ser", DRYNX_ASYNC_DISPATCH="serial")
    assert r_on == r_off == r_ser
    assert _sha(t_on) == _sha(t_off) == _sha(t_ser)
    assert set(t_on.values()) == {1}
    split = plane.SHARD_TIMERS.split_summary()
    assert split["host_glue_s"] > 0 and "WireDecode" in split["phases"]
    wr("complete", c_wall_on_s=round(w_on, 3), c_wall_off_s=round(w_off, 3),
       c_wall_serial_s=round(w_ser, 3), c_result=r_on,
       c_transcript_sha=_sha(t_on), c_bitmap_len=len(t_on), split=split)
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--delay-ms", type=float, default=None)
    ap.add_argument("--measure-child", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--paired", action="store_true")
    ap.add_argument("--record-path", default=None)
    args = ap.parse_args()
    if args.measure_child:
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()
        sys.exit(main_child(args))
    sys.exit(main_parent(args))


if __name__ == "__main__":
    main()
