#!/usr/bin/env python
"""Network-plane bench: serial vs parallel dispatch, JSON vs binary wire,
fresh vs pooled sockets/DRO — the PR-10 headline numbers (BENCH_NET_r01).

One supervised child per variant (bench.py pattern: the parent is jax-free
and survives child segfaults/timeouts; each child writes a progressive
record that the parent collects even from a corpse). Every child boots the
SAME in-process TCP roster — 3 CN / 8 DP / 3 VN — under a LinkModel that
charges real per-frame latency+bandwidth, and runs the same three surveys:

  A  sum, proofs off, 3 timed reps       -> dispatch wall clock (the
     stable-shape survey: freq's wider decode adds seconds of jitter)
  F  frequency_count, proofs off, 1 rep  -> wire bytes (tensor-heavy)
  B  sum with zero-noise diffp (lap_scale ~ 0 so every quantized draw is 0:
     the shuffle/DRO chain runs for real, the result stays exact)
     -> DRO precompute accounting (pooled child must serve from slabs)
  C  sum with proofs on (range/agg/ks)   -> normalized VN transcript

Variants (env-driven, exactly the production kill-switches):

  serial-json-fresh     DRYNX_FANOUT=serial DRYNX_WIRE=json  pool off
  parallel-json-fresh                        DRYNX_WIRE=json  pool off
  serial-v2-fresh       DRYNX_FANOUT=serial                   pool off
  parallel-v2-fresh                                           pool off
  parallel-v2-pooled    conn pool on + CryptoPool-backed CNs

The parent then checks the PR's acceptance bars: parallel >= 2x faster than
serial (same wire), v2 >= 25% fewer bytes than v1 (LinkModel-accounted),
serial/parallel byte-identical traffic, identical results everywhere,
identical VN transcripts, and zero fresh DRO precomputes in the pooled
child outside the refill lane.

Children run opt-level 0 + AVX2 + a persistent compile cache (the tier-1
test environment): survey A is link-dominated by design, so the dispatch
ratio is insensitive to kernel speed, and proofs-on C compiles in minutes
instead of tens of minutes after the first child seeds the cache.

Usage:
  python scripts/bench_net_plane.py            # full run -> BENCH_NET_r01.json
  python scripts/bench_net_plane.py --smoke    # <1 min check.sh tier
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

RECORD = os.path.join(ROOT, "BENCH_NET_r01.json")

ROLES = ["cn"] * 3 + ["dp"] * 8 + ["vn"] * 3
SMOKE_ROLES = ["cn", "cn", "dp", "dp", "dp"]
DATA_SEED = 77
DP_ROWS = 8
DIFFP_NOISE = 8          # noise_list_size per CN -> 3*8 pooled elems
A_REPS = 3
LINK_DELAY_MS = 300.0    # per-frame latency: the WAN point where dispatch
                         # structure (sum- vs max-over-nodes) is the story
LINK_MBPS = 100.0
SMOKE_DELAY_MS = 50.0
CHILD_TIMEOUT_S = 3000.0  # first proofs child compiles cold (policy
                          # COLD_COMPILE_WAIT_S-scale); later children
                          # ride the shared persistent cache

# (name, child env overrides, runs proofs-on C, runs diffp B).
# B runs only where the acceptance comparison needs it — the fresh
# baseline and the pooled child — because the fresh DRO precompute it
# measures costs ~10 min of execution per child at opt-level 0.
VARIANTS = [
    ("serial-json-fresh",
     {"DRYNX_FANOUT": "serial", "DRYNX_WIRE": "json",
      "DRYNX_CONN_POOL": "off"}, True, True),
    ("parallel-json-fresh",
     {"DRYNX_WIRE": "json", "DRYNX_CONN_POOL": "off"}, False, False),
    ("serial-v2-fresh",
     {"DRYNX_FANOUT": "serial", "DRYNX_CONN_POOL": "off"}, False, False),
    ("parallel-v2-fresh", {"DRYNX_CONN_POOL": "off"}, True, False),
    ("parallel-v2-pooled", {}, True, True),
]


def log(msg):
    print(f"[net-plane] {msg}", file=sys.stderr, flush=True)


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


def _child_env(overrides, delay_ms, mbps):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        flags += " --xla_cpu_max_isa=AVX2"
    if "xla_backend_optimization_level" not in flags:
        # opt 0: survey A is link-dominated (identical kernels on every
        # variant), and proofs-on C would otherwise compile for tens of
        # minutes per child on this box
        flags += " --xla_backend_optimization_level=0"
    env["XLA_FLAGS"] = flags.strip()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    env["DRYNX_LINK_DELAY_MS"] = str(delay_ms)
    env["DRYNX_LINK_MBPS"] = str(mbps)
    for k in ("DRYNX_FANOUT", "DRYNX_WIRE", "DRYNX_CONN_POOL"):
        env.pop(k, None)
    env.update(overrides)
    return env


def _compare(by):
    """Acceptance comparisons over the per-variant records (full mode)."""
    cmp, accept = {}, {}

    def ok(name):
        return by.get(name, {}).get("status") == "ok"

    if ok("serial-v2-fresh") and ok("parallel-v2-fresh"):
        ser, par = by["serial-v2-fresh"], by["parallel-v2-fresh"]
        cmp["parallel_speedup_x"] = round(
            ser["a_wall_min_s"] / par["a_wall_min_s"], 2)
        accept["parallel_2x_faster"] = cmp["parallel_speedup_x"] >= 2.0
        cmp["serial_parallel_bytes_equal"] = (
            ser["a_bytes"] == par["a_bytes"]
            and ser["a_by_peer"] == par["a_by_peer"])
        accept["dispatch_byte_identical"] = cmp["serial_parallel_bytes_equal"]
    if ok("parallel-json-fresh") and ok("parallel-v2-fresh"):
        v1 = by["parallel-json-fresh"]["f_bytes"]
        v2 = by["parallel-v2-fresh"]["f_bytes"]
        cmp["v2_byte_saving"] = round(1.0 - v2 / v1, 3)
        accept["v2_25pct_fewer_bytes"] = cmp["v2_byte_saving"] >= 0.25
    for key in ("a_result_sha", "f_result_sha"):
        shas = {n: r.get(key) for n, r in by.items() if ok(n)}
        cmp[key + "s"] = shas
        accept.setdefault("results_identical", True)
        accept["results_identical"] &= \
            len(set(shas.values())) == 1 and bool(shas)
    # B runs only in the fresh baseline and the pooled child
    bshas = {n: r["b_result_sha"] for n, r in by.items()
             if ok(n) and r.get("b_result_sha")}
    cmp["b_result_shas"] = bshas
    accept["diffp_results_identical"] = \
        len(set(bshas.values())) == 1 and len(bshas) >= 2
    bwalls = {n: r["b_wall_s"] for n, r in by.items()
              if ok(n) and r.get("b_wall_s") is not None}
    if ok("serial-json-fresh") and ok("parallel-v2-pooled"):
        # fresh pays the DRO precompute inline; pooled serves from slabs
        cmp["pooled_b_speedup_x"] = round(
            bwalls["serial-json-fresh"] / bwalls["parallel-v2-pooled"], 1)
    tshas = {n: r["c_transcript_sha"] for n, r in by.items()
             if ok(n) and r.get("c_transcript_sha")}
    cmp["c_transcript_shas"] = tshas
    accept["transcripts_identical"] = \
        len(set(tshas.values())) == 1 and len(tshas) >= 2
    if ok("parallel-v2-pooled"):
        p = by["parallel-v2-pooled"]
        accept["pooled_zero_fresh_precompute"] = \
            p["b_precompute_delta"] == 0 \
            and p["b_elements_consumed"] == 3 * DIFFP_NOISE
        accept["pooled_sockets_reused"] = p["conn_pool"]["reuses"] > 0
        if ok("parallel-v2-fresh"):
            # warm sockets skip per-call hello traffic the fresh pair pays
            accept["pooled_sockets_reused"] &= \
                p["f_bytes"] < by["parallel-v2-fresh"]["f_bytes"]
    return cmp, accept


def main_parent(args):
    _arm_parent()
    delay = args.delay_ms or (SMOKE_DELAY_MS if args.smoke
                              else LINK_DELAY_MS)
    timeout = args.timeout or (240 if args.smoke else CHILD_TIMEOUT_S)
    doc = {"round": "r01", "bench": "net_plane", "smoke": bool(args.smoke),
           "roster": {r: (SMOKE_ROLES if args.smoke else ROLES).count(r)
                      for r in ("cn", "dp", "vn")},
           "link": {"delay_ms": delay, "mbps": LINK_MBPS},
           "child_timeout_s": timeout, "variants": []}
    record_path = os.path.join(ROOT, ".net_plane_record.json")
    out = args.out or RECORD

    plan = [("smoke", {}, False, False)] if args.smoke else VARIANTS
    for name, overrides, proofs, diffp in plan:
        try:
            os.remove(record_path)
        except OSError:
            pass
        env = _child_env(overrides, delay, LINK_MBPS)
        cmd = [sys.executable, os.path.abspath(__file__), "--measure-child",
               "--variant", name, "--record-path", record_path]
        if args.smoke:
            cmd.append("--smoke")
        if proofs:
            cmd.append("--proofs")
        if diffp:
            cmd.append("--diffp")
        if name == "parallel-v2-pooled":
            cmd.append("--pooled")
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

    by = {v["variant"]: v for v in doc["variants"]}
    bad = [v["variant"] for v in doc["variants"] if v["status"] != "ok"]
    if args.smoke:
        log(f"smoke done: {len(bad)} bad")
        return 1 if bad else 0
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


def _boot(roles, tmpdir, pool=None):
    import numpy as np
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.service.node import DrynxNode, RosterEntry

    rng = np.random.default_rng(DATA_SEED)
    nodes, entries, datas = [], [], []
    for i, role in enumerate(roles):
        x, pub = eg.keygen(rng)
        data = None
        if role == "dp":
            data = rng.integers(0, 10, size=(DP_ROWS,)).astype(np.int64)
            datas.append(data)
        n = DrynxNode(f"{role}{i}", x, pub, data=data,
                      db_path=os.path.join(tmpdir, f"{role}{i}.db"),
                      pool=pool if role == "cn" else None)
        n.start()
        entries.append(RosterEntry(name=f"{role}{i}", role=role,
                                   host=n.address[0], port=n.address[1],
                                   public=pub))
        nodes.append(n)
    return nodes, entries, datas, rng


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


def main_child(args):
    global _REC_PATH
    _REC_PATH = args.record_path
    import tempfile

    import numpy as np  # noqa: F401
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import dro
    from drynx_tpu.resilience import policy as rp
    from drynx_tpu.service import transport as tp
    from drynx_tpu.service.node import RemoteClient, Roster

    roles = SMOKE_ROLES if args.smoke else ROLES
    tmpdir = tempfile.mkdtemp(prefix="net_plane_")
    pool = None
    if args.pooled:
        from drynx_tpu import pool as pool_mod
        pool = pool_mod.CryptoPool(os.path.join(tmpdir, "pool"),
                                   slab_elems=DIFFP_NOISE)
    wr("boot", variant=args.variant, roles=roles, pooled=bool(args.pooled),
       wire_env=os.environ.get("DRYNX_WIRE", ""),
       fanout_env=os.environ.get("DRYNX_FANOUT", ""),
       link={"delay_ms": float(os.environ.get("DRYNX_LINK_DELAY_MS", 0)),
             "mbps": float(os.environ.get("DRYNX_LINK_MBPS", 0))})
    nodes, entries, datas, rng = _boot(roles, tmpdir, pool=pool)
    roster = Roster(entries)
    client = RemoteClient(roster, rng)
    client.broadcast_roster()
    dl = eg.DecryptionTable(limit=1000)   # 8 DPs x 8 rows x max 9 = 576
    diffp = {"noise_list_size": DIFFP_NOISE, "lap_mean": 0.0,
             "lap_scale": 1e-9, "quanta": 1.0, "scale": 1.0, "limit": 4.0}

    def run(op, sid, **kw):
        t0 = time.time()
        res = client.run_survey(op, query_min=0, query_max=9,
                                survey_id=sid, dlog=dl, **kw)
        return res, time.time() - t0, dict(client.last_net)

    try:
        # -- warmup (forced serial: first kernel traces off the fan-out;
        # each measured shape warms once) ---------------------------------
        t0 = time.time()
        with _serial_dispatch():
            warm_res, dt, _ = run("frequency_count", "warm-f")
            wr("warm_f", warm_f_s=round(dt, 1))
            if not args.smoke:
                _, dt, _ = run("sum", "warm-a")
                wr("warm_a", warm_a_s=round(dt, 1))

        if args.smoke:
            wr("warm", warmup_s=round(time.time() - t0, 1))
            return _smoke_body(args, client, run, warm_res)

        if pool is not None:
            # refill lane: the only place fresh DRO precompute is allowed.
            # One refill covers warm-b AND the measured survey B (24 elems
            # each), so the pooled child never executes the fresh path.
            import jax

            from drynx_tpu.pool import replenish
            cn0 = nodes[0]
            tbl = cn0._pub_table(roster.collective_pub())
            pre = dro.PRECOMPUTE_CALLS
            replenish.refill_to(pool, jax.random.PRNGKey(3), tbl.table,
                                2 * 3 * DIFFP_NOISE)
            wr("refill", b_precompute_refill=dro.PRECOMPUTE_CALLS - pre)

        if args.diffp:
            # warm the diffp chain after the refill: pooled children serve
            # it from slabs; fresh children pay the counted cold path here
            with _serial_dispatch():
                _, dt, _ = run("sum", "warm-b", diffp=dict(diffp))
                wr("warm_b", warm_b_s=round(dt, 1))
        wr("warm", warmup_s=round(time.time() - t0, 1))

        # -- survey A: proofs-off dispatch wall clock --------------------
        walls, byts, msgs, by_peer, res = [], [], [], {}, None
        for i in range(A_REPS):
            res, dt, net = run("sum", f"a{i}")
            walls.append(round(dt, 3))
            byts.append(net["bytes_total"])
            msgs.append(net["msgs_total"])
            by_peer = net["by_peer"]
        wr("survey_a", a_wall_s=walls, a_wall_min_s=min(walls),
           a_bytes=byts, a_msgs=msgs, a_by_peer=by_peer,
           a_result_sha=_sha(int(res)))

        # -- survey F: tensor-heavy payloads -> wire byte accounting -----
        fres, fdt, fnet = run("frequency_count", "f0")
        wr("survey_f", f_wall_s=round(fdt, 3),
           f_bytes=fnet["bytes_total"], f_msgs=fnet["msgs_total"],
           f_by_peer=fnet["by_peer"], f_result_sha=_sha(fres))

        # -- survey B: diffp (zero-noise) -> DRO accounting --------------
        if args.diffp:
            pre = dro.PRECOMPUTE_CALLS
            consumed0 = pool.counters["elements_consumed"] \
                if pool is not None else 0
            t0 = time.time()
            bres = client.run_survey("sum", query_min=0, query_max=9,
                                     survey_id="b", diffp=dict(diffp),
                                     dlog=dl)
            bnet = dict(client.last_net)
            fields = dict(b_wall_s=round(time.time() - t0, 3),
                          b_bytes=bnet["bytes_total"], b_result=int(bres),
                          b_result_sha=_sha(int(bres)),
                          b_precompute_delta=dro.PRECOMPUTE_CALLS - pre)
            if pool is not None:
                fields["b_elements_consumed"] = \
                    pool.counters["elements_consumed"] - consumed0
                fields["conn_pool"] = tp.conn_pool().stats()
            wr("survey_b", **fields)

        # -- survey C: proofs on -> normalized VN transcript -------------
        if args.proofs:
            with _serial_dispatch():   # first proof-kernel traces
                client.run_survey("sum", query_min=0, query_max=9,
                                  proofs=True, ranges=[(4, 4)],
                                  survey_id="warm-c", dlog=dl,
                                  timeout=rp.COLD_COMPILE_WAIT_S)
            t0 = time.time()
            cres, block = client.run_survey(
                "sum", query_min=0, query_max=9, proofs=True,
                ranges=[(4, 4)], survey_id="bench-c", dlog=dl,
                timeout=rp.COLD_COMPILE_WAIT_S)
            norm = {k.replace("bench-c", "SID"): v
                    for k, v in block["bitmap"].items()}
            wr("survey_c", c_wall_s=round(time.time() - t0, 3),
               c_result=int(cres), c_bitmap_len=len(norm),
               c_all_true=set(norm.values()) == {1},
               c_transcript_sha=_sha(norm))
        wr("complete")
        return 0
    finally:
        tp.set_conn_pool(None)
        for n in nodes:
            n.stop()


def _smoke_body(args, client, run, warm_res):
    """One child, three in-process dispatch/wire variants of the same
    survey. Pre-commit gates must be deterministic, so the asserts cover
    the invariants (result identity, serial==parallel byte accounting,
    v2 < v1 bytes); wall clocks are recorded, not asserted — the full
    bench enforces the 2x bar on the link-dominated roster."""
    from drynx_tpu.service import transport as tp

    def variant(sid, **env):
        tp.set_conn_pool(None)
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            return run("frequency_count", sid)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    res_ser, w_ser, net_ser = variant("sm-ser", DRYNX_FANOUT="serial")
    res_par, w_par, net_par = variant("sm-par")
    res_v1, w_v1, net_v1 = variant("sm-v1", DRYNX_WIRE="json")
    assert _sha(res_ser) == _sha(res_par) == _sha(res_v1) == _sha(warm_res)
    assert net_ser["bytes_total"] == net_par["bytes_total"]
    assert net_ser["by_peer"] == net_par["by_peer"]
    assert net_par["bytes_total"] < 0.75 * net_v1["bytes_total"]
    wr("complete", f_wall_serial_s=round(w_ser, 3),
       f_wall_parallel_s=round(w_par, 3),
       f_bytes_v2=net_par["bytes_total"], f_bytes_v1=net_v1["bytes_total"],
       f_result_sha=_sha(res_par))
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--delay-ms", type=float, default=None)
    ap.add_argument("--measure-child", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--proofs", action="store_true")
    ap.add_argument("--diffp", action="store_true")
    ap.add_argument("--pooled", action="store_true")
    ap.add_argument("--record-path", default=None)
    args = ap.parse_args()
    if args.measure_child:
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()
        sys.exit(main_child(args))
    sys.exit(main_parent(args))


if __name__ == "__main__":
    main()
