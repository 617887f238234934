#!/usr/bin/env python
"""Saturation-serving bench: the PR-12 headline numbers (BENCH_LOAD_r01).

One supervised child per section (bench.py pattern: the parent is jax-free
and survives child segfaults/timeouts; each child writes a progressive
record the parent collects even from a corpse). Four sections run against
the calibrated SyntheticCluster (encode is a drain-thread wait, verify a
worker-side blocking wait — the shape of remote-VN RTTs and proof-thread
joins — so sweeps finish in seconds and are meaningful on a 1-core host);
the fifth runs real crypto:

  sweep        open-loop offered-load ladder -> throughput/latency curve;
               the headline is the highest measured completed rate whose
               p99 offer->done latency meets the SLO
  workers      closed-loop saturation at 1/2/4 verify workers -> the
               worker-count scaling curve (N>1 must beat 1)
  fairness     adversarial tenant mix (one hot tenant offering ~10x the
               others) -> per-tenant service counts; deficit round-robin
               plus quotas must keep the victims' fairness ratio bounded
  overload     a 5x burst far over capacity against a shallow queue ->
               typed sheds with positive retry-after hints and ZERO lost
               admitted surveys
  transcripts  real proofs-on LocalCluster: the same three surveys
               verified by a 1-worker and a 2-worker server must produce
               byte-identical per-survey VN transcripts (the cross-survey
               joint-RLC flush is grouping-invariant)

Children run opt-level 0 + AVX2 + the shared persistent compile cache;
only the transcripts child touches jax kernels (and rides the cache the
other benches seeded).

Usage:
  python scripts/bench_load.py            # full run -> BENCH_LOAD_r01.json
  python scripts/bench_load.py --smoke    # <1 min check.sh tier
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import bench  # noqa: E402  (jax-free supervisor helpers)

RECORD = os.path.join(ROOT, "BENCH_LOAD_r01.json")

SLO_P99_S = 0.5          # the headline's latency bar (offer -> done)
ENCODE_S = 0.002         # calibrated synthetic costs: drain-thread encode
VERIFY_S = 0.02          # and worker-side verify wait per survey
SWEEP_RATES = (40.0, 70.0, 100.0, 130.0)   # ladder brackets ~100 sps
SWEEP_DURATION_S = 6.0   # per ladder point
WORKER_COUNTS = (1, 2, 4)
WORKERS_N_TOTAL = 400    # closed-loop surveys per worker-count point
WORKERS_CONCURRENCY = 24
FAIR_RATE = 140.0        # over the 2-worker ~100 sps capacity
FAIR_DURATION_S = 6.0
OVER_RATE = 60.0
OVER_BURST = (2.0, 4.0, 5.0)   # 5x episode mid-run -> 300 sps offered
OVER_DURATION_S = 6.0
CHILD_TIMEOUT_S = 300.0
TRANSCRIPT_TIMEOUT_S = 3000.0  # cold proofs compile; warm cache -> minutes

# (section, timeout key). The synthetic sections are cheap; transcripts
# compiles real kernels on a cold cache.
SECTIONS = ["sweep", "workers", "fairness", "overload", "transcripts"]


def log(msg):
    print(f"[bench-load] {msg}", file=sys.stderr, flush=True)


def write_progressive(path, doc):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)


def section_result(name, outcome, rc, elapsed_s, record):
    rec = dict(record or {})
    stage = rec.pop("stage", None)
    base = {"section": name, "outcome": outcome, "rc": rc,
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


def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        flags += " --xla_cpu_max_isa=AVX2"
    if "xla_backend_optimization_level" not in flags:
        # opt 0: the tier-1 environment; transcripts would otherwise
        # compile for tens of minutes on this box
        flags += " --xla_backend_optimization_level=0"
    env["XLA_FLAGS"] = flags.strip()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    # the sections construct servers with explicit knobs; a stray
    # operator override must not skew the curve
    for k in ("DRYNX_VERIFY_WORKERS", "DRYNX_TENANT_QUOTA",
              "DRYNX_SHED_FRACTION"):
        env.pop(k, None)
    return env


def _lost_everywhere(by):
    """Sum of lost admitted surveys across every synthetic report in the
    run — the first overload gate, and it must be zero."""
    lost = 0
    for rec in by.values():
        if rec.get("status") != "ok":
            continue
        for key in ("points", "runs"):
            for p in rec.get(key, []):
                lost += p.get("lost", 0)
        for key in ("report",):
            if key in rec:
                lost += rec[key].get("lost", 0)
    return lost


def _compare(by):
    """Acceptance comparisons over the per-section records (full mode)."""
    cmp, accept = {}, {}

    def ok(name):
        return by.get(name, {}).get("status") == "ok"

    if ok("sweep"):
        pts = by["sweep"]["points"]
        meeting = [p for p in pts if p["p99_s"] <= SLO_P99_S
                   and p["lost"] == 0]
        over = [p for p in pts if p["p99_s"] > SLO_P99_S]
        headline = max((p["throughput_sps"] for p in meeting), default=0.0)
        cmp["headline_sps_at_p99_slo"] = headline
        cmp["slo_p99_s"] = SLO_P99_S
        accept["headline_measured"] = headline > 0.0
        # the ladder must actually cross saturation, or "max meeting the
        # SLO" is just "the biggest rate we tried"
        accept["sweep_crossed_saturation"] = len(over) >= 1
    if ok("workers"):
        runs = {r["workers"]: r for r in by["workers"]["runs"]}
        sps = {w: runs[w]["throughput_sps"] for w in runs}
        cmp["workers_sps"] = sps
        lo, hi = min(sps), max(sps)
        cmp["worker_scaling_x"] = round(sps[hi] / max(sps[lo], 1e-9), 2)
        accept["workers_n_beats_1"] = sps[hi] >= 1.25 * sps[lo]
    if ok("fairness"):
        f = by["fairness"]
        cmp["fairness_ratio"] = f["fairness_ratio"]
        cmp["hot_rejected"] = f["hot_rejected"]
        accept["fairness_victims_served"] = (
            f["fairness_ratio"] >= 0.5 and f["victims_all_served"])
        accept["fairness_hot_tenant_throttled"] = f["hot_rejected"] > 0
    if ok("overload"):
        r = by["overload"]["report"]
        cmp["overload_shed"] = r["rejected"]["shed"]
        accept["overload_sheds_typed"] = r["rejected"]["shed"] > 0
        accept["overload_hints_positive"] = \
            by["overload"]["min_retry_after_s"] > 0.0
        accept["overload_admitted_all_complete"] = (
            r["completed"] + r["errors"] == r["admitted"])
    accept["zero_lost_everywhere"] = _lost_everywhere(by) == 0
    if ok("transcripts"):
        t = by["transcripts"]
        cmp["transcript_digests_w1"] = t["digests_w1"]
        accept["transcripts_identical_across_workers"] = (
            t["digests_w1"] == t["digests_w2"]
            and len(t["digests_w1"]) >= 3
            and t["results_w1"] == t["results_w2"])
    return cmp, accept


def main_parent(args):
    _arm_parent()
    doc = {"round": "r01", "bench": "load", "smoke": bool(args.smoke),
           "slo_p99_s": SLO_P99_S,
           "synthetic_costs": {"encode_s": ENCODE_S, "verify_s": VERIFY_S},
           "basis": ("SyntheticCluster: verify modeled as worker-side "
                     "blocking waits (remote-VN RTT shape) so worker "
                     "scaling is measurable on a 1-core host; the "
                     "transcripts section runs real crypto"),
           "sections": []}
    record_path = os.path.join(ROOT, ".bench_load_record.json")
    out = args.out or RECORD
    env = _child_env()

    plan = ["smoke"] if args.smoke else list(SECTIONS)
    for name in plan:
        try:
            os.remove(record_path)
        except OSError:
            pass
        timeout = args.timeout or (
            60.0 if args.smoke else
            TRANSCRIPT_TIMEOUT_S if name == "transcripts" else
            CHILD_TIMEOUT_S)
        cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
               "--record-path", record_path]
        log(f"{name}: starting child (timeout {timeout:.0f}s)")
        outcome, rc, elapsed, _out = bench.supervise_child(
            cmd, timeout, env=env)
        st = section_result(name, outcome, rc, elapsed,
                            bench.read_record(record_path))
        print(json.dumps(st), flush=True)
        doc["sections"].append(st)
        if not args.smoke or args.out:
            write_progressive(out, doc)
    try:
        os.remove(record_path)
    except OSError:
        pass

    by = {s["section"]: s for s in doc["sections"]}
    bad = [s["section"] for s in doc["sections"] if s["status"] != "ok"]
    if args.smoke:
        gates = by.get("smoke", {}).get("accept", {})
        failed = [k for k, v in gates.items() if not v]
        log(f"smoke done: bad={bad} accept_failed={failed}")
        return 1 if bad or failed or not gates else 0
    cmp, accept = _compare(by)
    doc["comparisons"], doc["accept"] = cmp, accept
    doc["headline"] = {
        "max_sps_at_p99_slo": cmp.get("headline_sps_at_p99_slo", 0.0),
        "slo_p99_s": SLO_P99_S,
        "worker_scaling_x": cmp.get("worker_scaling_x", 0.0),
    }
    write_progressive(out, doc)
    print(json.dumps({"comparisons": cmp, "accept": accept}), flush=True)
    failed = [k for k, v in accept.items() if not v]
    log(f"done: {len(doc['sections'])} sections, bad={bad}, "
        f"accept_failed={failed}")
    return 1 if bad or failed else 0


# ---------------------------------------------------------------------------
# Children (all drynx_tpu imports below)
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


def _mix():
    from drynx_tpu.server.loadgen import ShapeMix
    return [ShapeMix("r42", weight=3.0, ranges=((4, 2),)),
            ShapeMix("r43", weight=1.0, ranges=((4, 3),)),
            ShapeMix("off", weight=1.0, proofs=0)]


def _server(cluster, **kw):
    from drynx_tpu.server.scheduler import SurveyServer
    kw.setdefault("max_batch", 4)
    return SurveyServer(cluster, **kw)


def _prewarm(srv, shapes):
    from drynx_tpu.server.loadgen import prewarm_shapes, synthetic_query
    prewarm_shapes(srv, [synthetic_query(f"warm-{s.name}", proofs=s.proofs,
                                         ranges=s.ranges)
                         for s in shapes])


def _point(rep):
    return {"offered": rep["offered"], "admitted": rep["admitted"],
            "completed": rep["completed"], "lost": rep["lost"],
            "rejected": rep["rejected"],
            "throughput_sps": rep["throughput_sps"],
            "p50_s": rep["latency_s"]["p50"],
            "p99_s": rep["latency_s"]["p99"]}


def child_sweep(duration_s=SWEEP_DURATION_S, rates=SWEEP_RATES):
    from drynx_tpu.server.loadgen import LoadGen, SyntheticCluster
    shapes = _mix()
    points = []
    for rate in rates:
        cl = SyntheticCluster(encode_s=ENCODE_S, verify_s=VERIFY_S)
        srv = _server(cl, max_depth=64, workers=2, tenant_quota=64)
        _prewarm(srv, shapes)
        lg = LoadGen(srv, shapes=shapes, seed=int(rate))
        rep = lg.run_open(rate, duration_s)
        points.append({"rate_sps": rate, **_point(rep)})
        wr("sweep", points=points)
    wr("complete", points=points)
    return 0


def child_workers(n_total=WORKERS_N_TOTAL, counts=WORKER_COUNTS):
    from drynx_tpu.server.loadgen import LoadGen, SyntheticCluster
    shapes = _mix()
    runs = []
    for w in counts:
        cl = SyntheticCluster(encode_s=ENCODE_S, verify_s=VERIFY_S)
        srv = _server(cl, max_depth=64, workers=w, tenant_quota=64)
        _prewarm(srv, shapes)
        lg = LoadGen(srv, shapes=shapes, seed=w)
        rep = lg.run_closed(WORKERS_CONCURRENCY, n_total)
        runs.append({"workers": w, **_point(rep)})
        wr("workers", runs=runs)
    wr("complete", runs=runs)
    return 0


def child_fairness(duration_s=FAIR_DURATION_S, rate=FAIR_RATE):
    from drynx_tpu.server.loadgen import (LoadGen, SyntheticCluster,
                                          fairness_ratio)
    shapes = _mix()
    victims = ["t1", "t2", "t3"]
    cl = SyntheticCluster(encode_s=ENCODE_S, verify_s=VERIFY_S)
    # shed off (fraction 1.0) so the quota + DRR story is isolated: the
    # hot tenant must hit ITS quota while the victims keep flowing
    srv = _server(cl, max_depth=32, workers=2, tenant_quota=6,
                  shed_fraction=1.0)
    _prewarm(srv, shapes)
    lg = LoadGen(srv, shapes=shapes, seed=7,
                 tenants={"hot": 10.0, "t1": 1.0, "t2": 1.0, "t3": 1.0})
    rep = lg.run_open(rate, duration_s)
    pt = rep["per_tenant"]
    wr("complete", report=rep, fairness_ratio=fairness_ratio(rep, victims),
       hot_rejected=pt.get("hot", {}).get("rejected", 0),
       victims_all_served=all(
           pt.get(t, {}).get("completed", 0) > 0 for t in victims))
    return 0


def child_overload(duration_s=OVER_DURATION_S, rate=OVER_RATE,
                   burst=OVER_BURST):
    from drynx_tpu.server.loadgen import LoadGen, SyntheticCluster
    shapes = _mix()
    cl = SyntheticCluster(encode_s=ENCODE_S, verify_s=VERIFY_S)
    srv = _server(cl, max_depth=16, workers=2, tenant_quota=16)
    _prewarm(srv, shapes)
    lg = LoadGen(srv, shapes=shapes, seed=3)
    rep = lg.run_open(rate, duration_s, bursts=(burst,))
    sheds = [r.retry_after_s for r in lg.records if r.outcome == "shed"]
    wr("complete", report=rep,
       min_retry_after_s=round(min(sheds), 6) if sheds else 0.0,
       max_retry_after_s=round(max(sheds), 6) if sheds else 0.0)
    return 0


def child_transcripts():
    import numpy as np

    from drynx_tpu.server.scheduler import SurveyServer
    from drynx_tpu.server.transcript import transcript_digest
    from drynx_tpu.service.service import LocalCluster

    def boot():
        cl = LocalCluster(n_cns=2, n_dps=2, n_vns=2, seed=13,
                          dlog_limit=4000)
        rng = np.random.default_rng(5)
        for name, dp in cl.dps.items():
            dp.data = rng.integers(0, 4, size=(2,)).astype(np.int64)
        return cl

    def queries(cl):
        mk = cl.generate_survey_query
        return [mk("sum", query_min=0, query_max=15, proofs=1,
                   ranges=[(4, 2)], survey_id="s0"),
                mk("sum", query_min=0, query_max=15, proofs=1,
                   ranges=[(4, 2)], survey_id="s1"),
                mk("sum", query_min=0, query_max=15, proofs=1,
                   ranges=[(4, 3)], survey_id="s2")]

    sids = ("s0", "s1", "s2")
    out = {}
    for tag, workers in (("w1", 1), ("w2", 2)):
        wr(f"transcripts-{tag}")
        cl = boot()
        srv = SurveyServer(cl, max_batch=3, pipeline=True, workers=workers)
        srv.prewarm(queries(cl)[0])
        for sq in queries(cl):
            srv.submit(sq)
        results = srv.drain()
        out[f"digests_{tag}"] = {s: transcript_digest(cl.vns, s)
                                 for s in sids}
        out[f"results_{tag}"] = {s: int(results[s].result) for s in sids}
        wr(f"transcripts-{tag}-done", **out)
    wr("complete", **out)
    return 0


def child_smoke():
    """Compact synthetic pass for the check.sh tier: a bursty open-loop
    run against a shallow queue plus an adversarial-mix mini-run; the
    gates are the full run's, shrunk."""
    from drynx_tpu.server.loadgen import (LoadGen, SyntheticCluster,
                                          fairness_ratio)
    shapes = _mix()

    cl = SyntheticCluster(encode_s=ENCODE_S, verify_s=VERIFY_S)
    srv = _server(cl, max_depth=16, workers=2, tenant_quota=16)
    _prewarm(srv, shapes)
    lg = LoadGen(srv, shapes=shapes, seed=3)
    over = lg.run_open(120.0, 2.0, bursts=((0.5, 1.0, 4.0),))
    sheds = [r.retry_after_s for r in lg.records if r.outcome == "shed"]
    wr("smoke-overload", overload=_point(over))

    cl2 = SyntheticCluster(encode_s=ENCODE_S, verify_s=VERIFY_S)
    srv2 = _server(cl2, max_depth=32, workers=2, tenant_quota=4,
                   shed_fraction=1.0)
    _prewarm(srv2, shapes)
    victims = ["t1", "t2"]
    lg2 = LoadGen(srv2, shapes=shapes, seed=7,
                  tenants={"hot": 8.0, "t1": 1.0, "t2": 1.0})
    fair = lg2.run_open(120.0, 2.0)
    ratio = fairness_ratio(fair, victims)

    accept = {
        "zero_lost": over["lost"] == 0 and fair["lost"] == 0,
        "sheds_typed_with_hints": (over["rejected"]["shed"] > 0
                                   and min(sheds) > 0.0),
        "p99_recorded": over["latency_s"]["p99"] > 0.0,
        "fairness_bounded": ratio >= 0.4 and all(
            fair["per_tenant"].get(t, {}).get("completed", 0) > 0
            for t in victims),
    }
    wr("complete", overload=_point(over), fairness=_point(fair),
       fairness_ratio=ratio, accept=accept)
    return 0


def main_child(args):
    global _REC_PATH
    _REC_PATH = args.record_path
    wr("start")
    fn = {"sweep": child_sweep, "workers": child_workers,
          "fairness": child_fairness, "overload": child_overload,
          "transcripts": child_transcripts, "smoke": child_smoke}
    return fn[args.child]()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--child", choices=SECTIONS + ["smoke"])
    ap.add_argument("--record-path")
    ap.add_argument("--out")
    ap.add_argument("--timeout", type=float)
    args = ap.parse_args()
    if args.child:
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()
        sys.exit(main_child(args))
    sys.exit(main_parent(args))


if __name__ == "__main__":
    main()
