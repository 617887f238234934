#!/usr/bin/env python
"""Streaming surveys: pane-delta advance vs from-scratch — the PR-18
acceptance harness (BENCH_STREAM_r01).

One supervised child per scenario family (bench.py pattern: jax-free
parent survives child segfaults/timeouts; children write progressive
records):

  stream   The headline. A proofs-on LocalCluster (2 CNs, 2 DPs, 2 VNs)
           runs one standing stream at 600k rows/DP (48 panes x 12500
           rows, window = 48 panes). At steady state a 1-pane slide
           seals/encrypts/range-proves ONE pane per DP — its proofs are
           signed, delivered and audit-committed once, at seal time,
           under the stream-stable pane sid — then ships only the CN
           aggregation proofs under the advance sid; the from-scratch
           control (cold stream id, cold caches) pays the whole window.
           Gates: >= 10x wall-clock on the proofs-on path, delta result
           == from-scratch result == plain-count ground truth, and a
           restarted engine re-fed the same rows reproduces the SAME
           survey id, result, decrypted bytes, advance transcript AND
           every window pane's transcript (byte identity via seeded
           pane randomness), with O(delta) proof-create/verify
           counters.
  epsilon  The per-(DP, cohort) accountant: budget 1.0 at 0.01/advance
           admits EXACTLY 100 charges then raises typed
           EpsilonExhausted; a reopened ledger (simulated restart)
           replays the journal and keeps rejecting; 8 threads racing
           the last 0.01 of a second identity admit exactly one.
  diffp    A DiffP stream over a prefilled CryptoPool: every advance's
           DRO rerandomization consumes pool precompute —
           dro.PRECOMPUTE_CALLS stays flat across all advances (zero
           fresh precompute outside the refill lane) and the balance
           drains by exactly noise_list_size x n_cns per advance.

Usage:
  python scripts/bench_stream.py            # full -> BENCH_STREAM_r01.json
  python scripts/bench_stream.py --smoke    # ~1-2 min check.sh tier
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import bench  # noqa: E402  (jax-free supervisor helpers)

RECORD = os.path.join(ROOT, "BENCH_STREAM_r01.json")

DATA_SEED = 3
ENGINE_SEED = 21
CHILD_TIMEOUT_S = 3600.0  # the stream child range-proves ~300 pane blobs
                          # at (16, 4) on a cold CPU cache


def log(msg):
    print(f"[stream] {msg}", file=sys.stderr, flush=True)


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


def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        flags += " --xla_cpu_max_isa=AVX2"
    if "xla_backend_optimization_level" not in flags:
        flags += " --xla_backend_optimization_level=0"
    env["XLA_FLAGS"] = flags.strip()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    for k in ("DRYNX_PANE_WIDTH", "DRYNX_STREAM_WINDOW",
              "DRYNX_EPSILON_BUDGET", "DRYNX_EPSILON_PER_ADVANCE",
              "DRYNX_SLIDE_PACING"):
        env.pop(k, None)
    return env


def _compare(by):
    """Acceptance over the per-variant records (full mode)."""
    accept = {}

    def ok(name):
        return by.get(name, {}).get("status") == "ok"

    s = by.get("stream", {})
    accept["stream_speedup_10x"] = bool(
        ok("stream") and (s.get("speedup") or 0) >= 10.0)
    accept["stream_bytes_identical_across_restart"] = bool(
        ok("stream") and s.get("identity_ok"))
    accept["stream_delta_matches_scratch_and_truth"] = bool(
        ok("stream") and s.get("delta_matches_scratch")
        and s.get("matches_ground_truth"))
    accept["stream_advance_work_is_o_delta"] = bool(
        ok("stream") and s.get("steady_work_o_delta"))

    e = by.get("epsilon", {})
    accept["epsilon_exhausts_exactly_at_budget"] = bool(
        ok("epsilon") and e.get("exact_at_budget"))
    accept["epsilon_restart_replays_spent"] = bool(
        ok("epsilon") and e.get("restart_still_rejects"))
    accept["epsilon_thread_single_spend"] = bool(
        ok("epsilon") and e.get("thread_single_spend"))

    d = by.get("diffp", {})
    accept["diffp_zero_fresh_precompute"] = bool(
        ok("diffp") and d.get("pool_covered_all"))
    return accept


def main_parent(args):
    _arm_parent()
    timeout = args.timeout or (600 if args.smoke else CHILD_TIMEOUT_S)
    doc = {"round": "r01", "bench": "stream", "smoke": bool(args.smoke),
           "child_timeout_s": timeout, "variants": []}
    record_path = os.path.join(ROOT, ".stream_record.json")
    out = args.out or RECORD

    if args.smoke:
        plan = [("stream", ["--stream"]), ("epsilon", ["--epsilon"])]
    else:
        plan = [("stream", ["--stream"]), ("epsilon", ["--epsilon"]),
                ("diffp", ["--diffp"])]
    for name, extra in plan:
        try:
            os.remove(record_path)
        except OSError:
            pass
        cmd = [sys.executable, os.path.abspath(__file__), "--measure-child",
               "--variant", name, "--record-path", record_path] + extra
        if args.smoke:
            cmd.append("--smoke")
        log(f"{name}: starting child (timeout {timeout:.0f}s)")
        outcome, rc, elapsed, _out = bench.supervise_child(
            cmd, timeout, env=_child_env())
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

    by = {v["variant"]: v for v in doc["variants"]}
    bad = [v["variant"] for v in doc["variants"] if v["status"] != "ok"]
    if args.smoke:
        log(f"smoke done: {len(bad)} bad")
        return 1 if bad else 0
    accept = _compare(by)
    doc["accept"] = accept
    write_progressive(out, doc)
    print(json.dumps({"accept": accept}), flush=True)
    failed = [k for k, v in accept.items() if not v]
    log(f"done: {len(doc['variants'])} variants, bad={bad}, "
        f"accept_failed={failed}")
    return 1 if bad or failed else 0


# ---------------------------------------------------------------------------
# Children (all jax work below)
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


def child_stream(args):
    """Headline: steady-state 1-pane slide vs from-scratch, proofs on,
    plus the restart byte-identity control."""
    from collections import Counter

    import numpy as np
    from drynx_tpu.server.transcript import transcript_digest
    from drynx_tpu.service.service import LocalCluster
    from drynx_tpu.service.streaming import StreamEngine

    if args.smoke:
        V, PW, W = 4, 32, 3
        ranges, dlog, min_speedup = [(16, 2)] * V, 2000, 1.2
    else:
        V, PW, W = 16, 12500, 48          # 600k rows/DP in the window
        ranges, dlog, min_speedup = [(16, 4)] * V, 90000, 10.0
    t0 = time.time()
    cl = LocalCluster(n_cns=2, n_dps=2, n_vns=2, seed=7, dlog_limit=dlog)
    n_dps = len(cl.dp_idents)
    wr("cluster", v=V, pane_width=PW, window_panes=W,
       rows_per_dp_window=PW * W, cluster_s=round(time.time() - t0, 1))

    rng = np.random.default_rng(DATA_SEED)
    rows = {d.name: rng.integers(0, V, size=(W + 2, PW)).astype(np.int64)
            for d in cl.dp_idents}

    def mk(sid):
        return StreamEngine(cl, "frequency_count", 0, V - 1,
                            stream_id=sid, pane_width=PW, window_panes=W,
                            ranges=ranges, proofs=1, seed=ENGINE_SEED)

    # build to steady state: W panes seal, the window fills, and one
    # warmup slide dispatches the pane-delta programs (raw ct_add /
    # ct_sub at the window shape — the `precompile --panes` set) so the
    # timed slide measures steady state, not first-touch compiles
    eng = mk("hl")
    eng.feed({n: r[:W].reshape(-1) for n, r in rows.items()})
    t0 = time.time()
    a0 = eng.advance()
    build_s = time.time() - t0
    eng.feed({n: r[W].reshape(-1) for n, r in rows.items()})
    t0 = time.time()
    eng.advance()
    wr("built", build_s=round(build_s, 1),
       warm_slide_s=round(time.time() - t0, 1), window0=list(a0.window))

    # steady-state slide: ONE new pane per DP
    c0 = dict(eng.counters)
    eng.feed({n: r[W + 1].reshape(-1) for n, r in rows.items()})
    t0 = time.time()
    a1 = eng.advance()
    t_delta = time.time() - t0
    d_created = eng.counters["proofs_created"] - c0["proofs_created"]
    d_verified = eng.counters["pane_verifies"] - c0["pane_verifies"]
    steady_o_delta = (d_created == n_dps and d_verified <= n_dps
                      and a1.panes_new == 1 and a1.panes_expired == 1)
    wr("steady", advance_s=round(t_delta, 3),
       steady_proofs_created=d_created, steady_pane_verifies=d_verified,
       steady_work_o_delta=steady_o_delta, window1=list(a1.window))

    # from-scratch control: cold stream id = cold proof cache, cold
    # verdict memo, cold VN VerifyCache; same window CONTENT
    scratch = mk("hl-scratch")
    scratch.feed({n: r[2:W + 2].reshape(-1) for n, r in rows.items()})
    t0 = time.time()
    s1 = scratch.advance()
    t_scratch = time.time() - t0
    speedup = t_scratch / max(t_delta, 1e-9)
    truth = Counter()
    for r in rows.values():
        truth.update(r[2:W + 2].reshape(-1).tolist())
    want = {v: truth.get(v, 0) for v in range(V)}
    delta_matches = s1.result == a1.result
    truth_ok = a1.result == want
    wr("scratch", scratch_s=round(t_scratch, 1), speedup=round(speedup, 2),
       delta_matches_scratch=delta_matches, matches_ground_truth=truth_ok)

    # restart identity control: a FRESH engine, SAME stream id, re-fed
    # every row -> same survey id; seeded pane randomness must reproduce
    # result, decrypted bytes, the advance transcript AND every window
    # pane's seal-time transcript byte-identically (the re-delivered
    # pane payloads land under the same stream-stable pane sids)
    dig1 = transcript_digest(cl.vns, a1.survey_id)
    pane_digs = [transcript_digest(cl.vns, eng.pane_sid(p))
                 for p in range(a1.window[0], a1.window[1] + 1)]
    ident = mk("hl")
    ident.feed({n: r.reshape(-1) for n, r in rows.items()})
    i1 = ident.advance()
    identity_ok = (
        i1.survey_id == a1.survey_id and i1.result == a1.result
        and i1.decrypted.values.tobytes() == a1.decrypted.values.tobytes()
        and transcript_digest(cl.vns, i1.survey_id) == dig1
        and [transcript_digest(cl.vns, ident.pane_sid(p))
             for p in range(i1.window[0], i1.window[1] + 1)] == pane_digs)
    clean_bitmaps = (
        all(a.block is not None for a in (a0, a1, s1, i1))
        and all(p.block is not None for p in eng._panes))
    wr("complete", identity_ok=identity_ok, clean_bitmaps=clean_bitmaps,
       transcript_sha=dig1,
       counters={k: int(v) for k, v in eng.counters.items()})
    ok = (identity_ok and delta_matches and truth_ok and steady_o_delta
          and clean_bitmaps and speedup >= min_speedup)
    return 0 if ok else 1


def child_epsilon(args):
    """Accountant gates: exact exhaustion, restart replay, thread race."""
    import tempfile
    import threading

    from drynx_tpu import pool as pool_mod

    root = tempfile.mkdtemp(prefix="bench_eps_")
    budget, eps = 1.0, 0.01
    led = pool_mod.EpsilonLedger(root, budget=budget)
    admitted = 0
    try:
        while admitted < 10_000:
            led.charge("dp0", "cohortA", eps)
            admitted += 1
    except pool_mod.EpsilonExhausted:
        pass
    exact = admitted == round(budget / eps)
    wr("exhausted", charges_admitted=admitted, exact_at_budget=exact,
       spent=led.spent("dp0", "cohortA"))

    # simulated restart: a reopened ledger replays the fsync'd journal
    led2 = pool_mod.EpsilonLedger(root, budget=budget)
    still_rejects = False
    try:
        led2.charge("dp0", "cohortA", eps)
    except pool_mod.EpsilonExhausted:
        still_rejects = True
    replay_exact = abs(led2.spent("dp0", "cohortA")
                       - admitted * eps) < 1e-6
    wr("restart", restart_still_rejects=bool(still_rejects and replay_exact))

    # 8 threads race the last 0.01 of a second identity: exactly one wins
    led2.charge("dp1", "cohortA", budget - eps)
    barrier = threading.Barrier(8)
    wins, rejects = [], []

    def racer():
        barrier.wait()
        try:
            led2.charge("dp1", "cohortA", eps)
            wins.append(1)
        except pool_mod.EpsilonExhausted:
            rejects.append(1)

    threads = [threading.Thread(target=racer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    single = len(wins) == 1 and len(rejects) == 7
    wr("complete", thread_single_spend=single,
       ledger_counters={k: int(v) for k, v in led2.counters.items()})
    return 0 if (exact and still_rejects and replay_exact and single) else 1


def child_diffp(args):
    """DiffP stream over a prefilled pool: advances consume precompute,
    never generate it (PRECOMPUTE_CALLS flat outside the refill)."""
    import tempfile

    import jax
    import numpy as np
    from drynx_tpu import pool as pool_mod
    from drynx_tpu.parallel import dro
    from drynx_tpu.pool import replenish
    from drynx_tpu.service.query import DiffPParams
    from drynx_tpu.service.service import LocalCluster
    from drynx_tpu.service.streaming import StreamEngine

    root = tempfile.mkdtemp(prefix="bench_dro_")
    noise = 8
    pool = pool_mod.CryptoPool(root, slab_elems=noise)
    cl = LocalCluster(n_cns=2, n_dps=2, n_vns=0, seed=19, dlog_limit=2000,
                      pool=pool)
    n_adv = 4
    need = n_adv * len(cl.cns) * noise
    replenish.refill_to(pool, jax.random.PRNGKey(11), cl.coll_tbl.table,
                        need)
    dig = pool_mod.key_digest(cl.coll_tbl.table)
    bal0 = pool.dro_balance(dig)
    wr("filled", prefilled_elems=int(bal0))
    diffp = DiffPParams(noise_list_size=noise, lap_mean=0.0, lap_scale=2.0,
                        quanta=1.0, scale=1.0, limit=4.0)
    eng = StreamEngine(cl, "frequency_count", 0, 3, stream_id="dp-stream",
                       pane_width=16, window_panes=2, proofs=0,
                       diffp=diffp, seed=ENGINE_SEED)
    rng = np.random.default_rng(9)
    before = dro.PRECOMPUTE_CALLS
    for _ in range(n_adv):
        eng.feed({d.name: rng.integers(0, 4, size=16).astype(np.int64)
                  for d in cl.dp_idents})
        eng.advance()
    flat = dro.PRECOMPUTE_CALLS == before
    drained = int(bal0) - int(pool.dro_balance(dig))
    wr("complete", advances=n_adv,
       precompute_calls_delta=int(dro.PRECOMPUTE_CALLS - before),
       pool_elems_drained=drained, pool_covered_all=bool(
           flat and drained == n_adv * len(cl.cns) * noise))
    return 0 if (flat and drained == need) else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--measure-child", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--stream", action="store_true")
    ap.add_argument("--epsilon", action="store_true")
    ap.add_argument("--diffp", action="store_true")
    ap.add_argument("--record-path", default=None)
    args = ap.parse_args()
    if args.measure_child:
        global _REC_PATH
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()
        _REC_PATH = args.record_path
        if args.epsilon:
            sys.exit(child_epsilon(args))
        if args.diffp:
            sys.exit(child_diffp(args))
        sys.exit(child_stream(args))
    sys.exit(main_parent(args))


if __name__ == "__main__":
    main()
