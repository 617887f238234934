#!/usr/bin/env python
"""Pause/revive soak: mid-survey partition tolerance under load — the
PR-17 acceptance harness (BENCH_SOAK_r01).

One supervised child per scenario family (bench.py pattern: jax-free
parent survives child segfaults/timeouts; children write progressive
records). Every fault below is a seeded, time-windowed episode from
resilience.faults — down at ``after_s``, healed ``heal_after_s`` later
on the plan clock — so the same seed replays the identical down/up
timeline:

  sched-soak  LocalCluster (proofs + VN trio) + SurveyServer under a
              closed-loop LoadGen driving REAL survey queries
              (``query_fn``). A DP kill window and a client<->DP
              partition window open mid-run; the scheduler's
              checkpointed resume lane (CHECKPOINT_MAX_RESUMES paced
              passes) re-enters the affected surveys from their phase
              checkpoints. Gates: zero admitted surveys lost, results
              AND VN transcripts byte-identical to a clean same-seed
              run, affected surveys show phase-counter resume evidence
              (probe entries > 1, resumes > 0), two same-seed faulted
              runs report identical episode timelines and accounting,
              and the durable checkpoint store reads back the final
              record after reopen (root-restart persistence).
  tree-soak   In-process TCP roster (1 CN + 7 DPs, fanout 2 — a
              3-level tree), three episodes: an interior relay killed
              with a heal window (its subtree re-parents onto the
              survivor layout, the healed relay is re-entered), a DP
              reply torn mid-frame AFTER its contribution computed
              (the reply cache must replay byte-identical bytes), and
              a root<->forest-root partition window. Gates: every
              episode heals to the exact full-roster sum with all DPs
              responding, collect re-entry counters prove resume (not
              restart), faulted results match the clean run, and the
              full sweep repeated with the same seed is identical.
  multiproc-soak  1 in-process root CN + 6 REAL `cmd/server run` DP
              subprocesses. The FaultPlan lives in the root's process,
              so kill/partition episodes sever the root's dials to
              live subprocess DPs exactly like a cut link. Gates: both
              episodes (interior relay, partition) heal to the exact
              sum with the full roster responding.

Usage:
  python scripts/bench_soak.py            # full -> BENCH_SOAK_r01.json
  python scripts/bench_soak.py --smoke    # ~60 s check.sh tier
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import bench  # noqa: E402  (jax-free supervisor helpers)

RECORD = os.path.join(ROOT, "BENCH_SOAK_r01.json")

SOAK_SEED = 23
DATA_SEED = 88
DP_ROWS = 8
TREE_DPS = 7             # fanout 2 -> a 3-level tree
MP_DPS = 6
SCHED_N_TOTAL = 8
SCHED_CONC = 2
CHILD_TIMEOUT_S = 3000.0  # the sched child compiles proof kernels cold
                          # on a cache miss; tree/multiproc are
                          # link-bound and finish in ~a minute


def log(msg):
    print(f"[soak] {msg}", file=sys.stderr, flush=True)


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
    for k in ("DRYNX_TOPOLOGY", "DRYNX_TREE_FANOUT", "DRYNX_FANOUT",
              "DRYNX_PROBE_TTL"):
        env.pop(k, None)
    return env


def _compare(by):
    """Acceptance over the per-variant records (full mode)."""
    accept = {}

    def ok(name):
        return by.get(name, {}).get("status") == "ok"

    s = by.get("sched-soak", {})
    accept["sched_zero_lost"] = bool(ok("sched-soak") and s.get("zero_lost"))
    accept["sched_results_and_transcripts_match_clean"] = \
        bool(ok("sched-soak") and s.get("results_match_clean"))
    accept["sched_resumed_from_checkpoint"] = \
        bool(ok("sched-soak") and s.get("resumed_from_checkpoint"))
    accept["sched_same_seed_identical"] = \
        bool(ok("sched-soak") and s.get("same_seed_identical"))
    accept["sched_checkpoint_durable"] = \
        bool(ok("sched-soak") and s.get("ckpt_durable"))

    t = by.get("tree-soak", {})
    accept["tree_all_episodes_heal"] = \
        bool(ok("tree-soak") and t.get("all_heal"))
    accept["tree_matches_clean"] = \
        bool(ok("tree-soak") and t.get("matches_clean"))
    accept["tree_same_seed_identical"] = \
        bool(ok("tree-soak") and t.get("same_seed_identical"))
    # >= 3 windowed episodes across the soak, including the interior
    # relay and the mid-contribution DP
    n_ep = (len(s.get("episodes") or [])
            + sum(len(v.get("episodes") or [])
                  for v in (t.get("faulted") or {}).values()))
    scen = set((t.get("faulted") or {}).keys())
    accept["episodes_cover_relay_and_midreply"] = bool(
        n_ep >= 3 and {"relay-kill", "dp-midreply",
                       "partition"} <= scen)

    m = by.get("multiproc-soak", {})
    accept["multiproc_heals"] = bool(ok("multiproc-soak")
                                     and m.get("all_heal"))
    return accept


def main_parent(args):
    _arm_parent()
    timeout = args.timeout or (420 if args.smoke else CHILD_TIMEOUT_S)
    doc = {"round": "r01", "bench": "soak", "smoke": bool(args.smoke),
           "seed": SOAK_SEED, "child_timeout_s": timeout, "variants": []}
    record_path = os.path.join(ROOT, ".soak_record.json")
    out = args.out or RECORD

    if args.smoke:
        plan = [("smoke", ["--tree"])]
    else:
        plan = [("sched-soak", ["--sched"]),
                ("tree-soak", ["--tree"]),
                ("multiproc-soak", ["--multiproc"])]
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


class _env:
    def __init__(self, **kv):
        self.kv = kv

    def __enter__(self):
        self.saved = {k: os.environ.get(k) for k in self.kv}
        os.environ.update(self.kv)

    def __exit__(self, *exc):
        for k, v in self.saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _soak_policy():
    """Seeded retry policy for roster nodes: deterministic jitter (two
    same-seed runs sleep identical schedules) and quick dead-dial
    verdicts so healing passes spend their budget probing, not backing
    off."""
    from drynx_tpu.resilience import policy as rp
    return rp.RetryPolicy(connect_retries=1, backoff_s=0.1,
                          backoff_cap_s=0.2, jitter=0.25,
                          call_timeout_s=rp.CALL_TIMEOUT_S,
                          seed=SOAK_SEED)


def _boot(roles, tmpdir):
    import numpy as np
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.service.node import DrynxNode, RosterEntry

    pol = _soak_policy()
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
                      policy=pol)
        n.start()
        entries.append(RosterEntry(name=f"{role}{i}", role=role,
                                   host=n.address[0], port=n.address[1],
                                   public=pub))
        nodes.append(n)
    return nodes, entries, datas, rng


def _share_pub_table(nodes, roster):
    coll = roster.collective_pub()
    tbl = nodes[0]._pub_table(coll)
    for n in nodes[1:]:
        n._tbl_cache = {coll: tbl}


def child_sched(args):
    """Checkpointed scheduler resume under closed-loop load: healing
    kill + partition windows over a proofs-on LocalCluster."""
    import tempfile

    import numpy as np
    from drynx_tpu.resilience import faults as fl
    from drynx_tpu.server.loadgen import LoadGen, ShapeMix
    from drynx_tpu.server.scheduler import SurveyServer
    from drynx_tpu.server.transcript import transcript_digest
    from drynx_tpu.service.service import LocalCluster
    from drynx_tpu.service.store import ProofDB, SurveyCheckpoint

    tmpdir = tempfile.mkdtemp(prefix="soak_sched_")
    ck_path = os.path.join(tmpdir, "ck.db")

    def mkplan():
        # two healing windows opening at the run epoch: dp1 dies and
        # revives, the client<->dp2 link is cut and restored. Strict
        # quorum (all DPs) makes degraded completion impossible — the
        # scheduler MUST ride the checkpointed resume lane across the
        # heal boundary or lose the survey.
        return fl.FaultPlan(seed=SOAK_SEED, specs=[
            fl.FaultSpec(where="node", kind="kill", target="dp1",
                         after_s=0.15, heal_after_s=0.7),
            fl.FaultSpec(where="node", kind="partition", target="*",
                         peer="dp2", after_s=0.0, heal_after_s=1.0)])

    def run(tag, plan, durable=False):
        fl.set_fault_plan(None)
        cl = LocalCluster(n_cns=2, n_dps=3, n_vns=2, seed=13,
                          dlog_limit=4000)
        rng = np.random.default_rng(5)
        for _name, dp in cl.dps.items():
            dp.data = rng.integers(0, 4, size=(2,)).astype(np.int64)
        if durable:
            cl.attach_checkpoint_store(ck_path)
        srv = SurveyServer(cl, max_batch=1, max_depth=16, pipeline=False)

        def qfn(sid, shape):
            return cl.generate_survey_query(
                "sum", query_min=0, query_max=15, proofs=1,
                ranges=[(4, 2)], survey_id=sid)

        lg = LoadGen(srv, shapes=[ShapeMix("s", proofs=1,
                                           ranges=((4, 2),))],
                     seed=SOAK_SEED, query_fn=qfn)
        srv.prewarm(qfn(f"{tag}-warm", None))
        if plan is not None:
            fl.set_fault_plan(plan)
            plan.reset_epoch()
        t0 = time.time()
        try:
            rep = lg.run_closed(concurrency=SCHED_CONC,
                                n_total=SCHED_N_TOTAL)
        finally:
            fl.set_fault_plan(None)
        res = srv.results()
        out = {
            "acct": {k: rep[k] for k in ("offered", "admitted",
                                         "completed", "errors", "lost")},
            "sums": {s: int(r.result) for s, r in sorted(res.items())},
            "digests": {s: transcript_digest(cl.vns, s)
                        for s in sorted(res)},
            "resumes": {s: int(r.resumes) for s, r in sorted(res.items())},
            "phases": {s: dict(r.phases) for s, r in sorted(res.items())},
            "episodes": plan.episodes() if plan is not None else [],
        }
        if durable:
            cl.checkpoint_db.close()
        wr(tag, **{f"{tag}_acct": out["acct"],
                   f"{tag}_wall_s": round(time.time() - t0, 1)})
        return out

    # short probe TTL: each paced resume pass re-probes instead of
    # dispatching on a verdict drawn before the heal boundary moved
    with _env(DRYNX_PROBE_TTL="0.2"):
        C = run("clean", None)
        A = run("faulted_a", mkplan(), durable=True)
        B = run("faulted_b", mkplan())

    affected = sorted(s for s, n in A["resumes"].items() if n > 0)
    db = ProofDB(ck_path)
    durable_ok = False
    if affected:
        ck = SurveyCheckpoint.load(db, affected[0])
        durable_ok = (ck is not None and ck.done
                      and ck.resumes == A["resumes"][affected[0]])
    db.close()

    zero_lost = all(R["acct"]["lost"] == 0 and R["acct"]["errors"] == 0
                    and R["acct"]["completed"] == SCHED_N_TOTAL
                    for R in (A, B, C))
    results_match = (A["sums"] == C["sums"]
                     and A["digests"] == C["digests"])
    resumed = (len(affected) >= 1
               and all(A["phases"][s].get("probe", 0) >= 2
                       for s in affected)
               and all(n == 0 for n in C["resumes"].values()))
    same_seed = (A["sums"] == B["sums"] and A["digests"] == B["digests"]
                 and A["acct"] == B["acct"]
                 and A["episodes"] == B["episodes"])
    wr("complete",
       episodes=A["episodes"], affected=affected,
       resumes=A["resumes"],
       affected_phases={s: A["phases"][s] for s in affected},
       sums_sha=_sha(A["sums"]), transcripts_sha=_sha(A["digests"]),
       zero_lost=zero_lost, results_match_clean=results_match,
       resumed_from_checkpoint=resumed, same_seed_identical=same_seed,
       ckpt_durable=durable_ok)
    return 0 if (zero_lost and results_match and resumed
                 and same_seed and durable_ok) else 1


def child_tree(args):
    """Three healing episodes over a 3-level in-process TCP tree: dead
    interior relay (survivor-layout failover), torn mid-contribution
    reply (cache replay), root<->forest-root partition."""
    import tempfile

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.resilience import faults as fl
    from drynx_tpu.service import transport as tp
    from drynx_tpu.service.node import RemoteClient, Roster

    tmpdir = tempfile.mkdtemp(prefix="soak_tree_")
    with _env(DRYNX_TREE_FANOUT="2"):
        nodes, entries, datas, rng = _boot(["cn"] + ["dp"] * TREE_DPS,
                                           tmpdir)
        roster = Roster(entries)
        _share_pub_table(nodes, roster)
        client = RemoteClient(roster, rng, policy=_soak_policy())
        client.broadcast_roster()
        dl = eg.DecryptionTable(limit=2000)
        want = int(sum(d.sum() for d in datas))
        order = [e.name for e in entries if e.role == "dp"]
        # fanout 2 over 7 DPs: order[0]/order[1] root the two subtrees
        # (interior relays); the tail of the order is leaves
        relay, root2, leaf = order[0], order[1], order[5]
        wr("boot", n_dps=TREE_DPS, want=want, relay=relay, leaf=leaf)

        def scenarios():
            return [
                ("relay-kill", fl.FaultPlan(seed=SOAK_SEED, specs=[
                    fl.FaultSpec(where="node", kind="kill", target=relay,
                                 after_s=0.0, heal_after_s=0.9)])),
                ("dp-midreply", fl.FaultPlan(seed=SOAK_SEED, specs=[
                    fl.FaultSpec(where="reply", kind="close_mid_frame",
                                 target=leaf, mtype="survey_dp",
                                 count=1)])),
                ("partition", fl.FaultPlan(seed=SOAK_SEED, specs=[
                    fl.FaultSpec(where="node", kind="partition",
                                 target="cn0", peer=root2,
                                 after_s=0.0, heal_after_s=0.8)])),
            ]

        def sweep(tag, faulted):
            out = {}
            for name, plan in scenarios():
                tp.set_conn_pool(None)
                if faulted:
                    fl.set_fault_plan(plan)
                    plan.reset_epoch()
                t0 = time.time()
                try:
                    res = client.run_survey("sum", query_min=0,
                                            query_max=9,
                                            survey_id=f"{tag}-{name}",
                                            dlog=dl)
                finally:
                    fl.set_fault_plan(None)
                out[name] = {
                    "result": int(res),
                    "responders": list(client.last_responders),
                    "absent": list(client.last_absent),
                    "collect_entries": int(
                        client.last_phases.get("collect", 0)),
                    "wall_s": round(time.time() - t0, 2),
                    "episodes": plan.episodes() if faulted else [],
                }
                wr(f"{tag}-{name}", **{f"{tag}_{name}": out[name]})
            return out

        def strip(sw):
            # the same-seed identity is over results + membership +
            # timelines; wall clocks are recorded, not compared
            return {k: {f: v[f] for f in ("result", "responders",
                                          "absent", "episodes")}
                    for k, v in sw.items()}

        try:
            res = client.run_survey("sum", query_min=0, query_max=9,
                                    survey_id="soak-warm", dlog=dl)
            assert int(res) == want
            wr("warm")
            FA = sweep("fa", True)
            CL = sweep("cl", False)
            all_heal = all(
                v["result"] == want and v["responders"] == order
                and v["absent"] == [] for v in FA.values())
            # the relay and partition episodes cross a heal boundary, so
            # collect must have been re-entered (resume, not restart);
            # the torn reply may heal inside the first dispatch wave
            all_heal = all_heal and all(
                FA[k]["collect_entries"] >= 2
                for k in ("relay-kill", "partition"))
            matches_clean = ({k: v["result"] for k, v in FA.items()}
                             == {k: v["result"] for k, v in CL.items()})
            fields = {"faulted": FA, "clean": CL, "all_heal": all_heal,
                      "matches_clean": matches_clean}
            if args.smoke:
                wr("complete", **fields)
                return 0 if (all_heal and matches_clean) else 1
            FB = sweep("fb", True)
            same_seed = strip(FA) == strip(FB)
            wr("complete", same_seed_identical=same_seed, **fields)
            return 0 if (all_heal and matches_clean and same_seed) else 1
        finally:
            tp.set_conn_pool(None)
            for n in nodes:
                n.stop()


def child_multiproc(args):
    """Healing episodes against a REAL multi-process roster: the root CN
    (in this process, where the FaultPlan lives) loses its links to
    `cmd/server run` DP subprocesses and re-enters them on heal."""
    import socket
    import tempfile

    import numpy as np
    from drynx_tpu.cmd import toml_io
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.resilience import faults as fl
    from drynx_tpu.service import transport as tp
    from drynx_tpu.service.node import (DrynxNode, RemoteClient, Roster,
                                        RosterEntry)

    tmpdir = tempfile.mkdtemp(prefix="soak_mp_")
    rng = np.random.default_rng(DATA_SEED)
    env = dict(os.environ)
    env["DRYNX_PROOF_PLANE"] = "off"
    # the node processes share this child's persistent cache
    from drynx_tpu.utils.cache import ENV_VAR, enable_compilation_cache
    env[ENV_VAR] = enable_compilation_cache()
    procs, entries, datas = [], [], []
    cn = None
    wr("boot", n_dps=MP_DPS)
    with _env(DRYNX_TREE_FANOUT="2"):
        try:
            # the root CN stays in-process: the seeded plan governs ITS
            # dials, so an episode makes a live subprocess DP
            # unreachable from the root exactly like a severed link
            x, pub = eg.keygen(rng)
            cn = DrynxNode("cn0", x, pub,
                           db_path=os.path.join(tmpdir, "cn0.db"),
                           policy=_soak_policy())
            cn.start()
            entries.append(RosterEntry(name="cn0", role="cn",
                                       host=cn.address[0],
                                       port=cn.address[1], public=pub))
            for i in range(MP_DPS):
                name = f"dp{i + 1}"
                s = socket.socket()
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
                s.close()
                x, pub = eg.keygen(rng)
                cfg = toml_io.dumps({"node": {
                    "name": name, "host": "127.0.0.1", "port": port,
                    "secret": hex(x), "public_x": hex(pub[0]),
                    "public_y": hex(pub[1])}})
                data = rng.integers(0, 10,
                                    size=(DP_ROWS,)).astype(np.int64)
                datas.append(data)
                df = os.path.join(tmpdir, f"{name}.txt")
                np.savetxt(df, data, fmt="%d")
                cmd = [sys.executable, "-m", "drynx_tpu.cmd.server",
                       "run", "--data", df]
                errlog = open(os.path.join(tmpdir, f"{name}.log"), "wb")
                p = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stderr=errlog, env=env, cwd=ROOT)
                p.stdin.write(cfg.encode())
                p.stdin.close()
                procs.append((name, p, errlog))
                entries.append(RosterEntry(name=name, role="dp",
                                           host="127.0.0.1", port=port,
                                           public=pub))
            deadline = time.time() + 120
            for name, p, _ in procs:
                lp = os.path.join(tmpdir, f"{name}.log")
                while True:
                    if (os.path.exists(lp)
                            and b"listening" in open(lp, "rb").read()):
                        break
                    if p.poll() is not None or time.time() > deadline:
                        raise RuntimeError(f"server {name} never came up")
                    time.sleep(0.2)
            wr("listening")
            roster = Roster(entries)
            client = RemoteClient(roster, rng, policy=_soak_policy())
            client.broadcast_roster()
            dl = eg.DecryptionTable(limit=3000)
            want = int(sum(d.sum() for d in datas))
            order = [e.name for e in entries if e.role == "dp"]
            relay, root2 = order[0], order[1]
            res = client.run_survey("sum", query_min=0, query_max=9,
                                    survey_id="mp-warm", dlog=dl)
            out = {"want": want, "warm_exact": int(res) == want}
            wr("warm", **out)
            scens = [
                ("relay-kill", fl.FaultPlan(seed=SOAK_SEED, specs=[
                    fl.FaultSpec(where="node", kind="kill", target=relay,
                                 after_s=0.0, heal_after_s=0.9)])),
                ("partition", fl.FaultPlan(seed=SOAK_SEED, specs=[
                    fl.FaultSpec(where="node", kind="partition",
                                 target="cn0", peer=root2,
                                 after_s=0.0, heal_after_s=0.8)])),
            ]
            for nm, plan in scens:
                # drop pooled sockets: kill episodes are enforced at
                # dial time, and a warm pooled conn to a live
                # subprocess DP would never re-dial
                tp.set_conn_pool(None)
                fl.set_fault_plan(plan)
                plan.reset_epoch()
                t0 = time.time()
                try:
                    r = client.run_survey("sum", query_min=0,
                                          query_max=9,
                                          survey_id=f"mp-{nm}", dlog=dl)
                finally:
                    fl.set_fault_plan(None)
                out[nm] = {
                    "result": int(r), "exact": int(r) == want,
                    "n_responders": len(client.last_responders),
                    "collect_entries": int(
                        client.last_phases.get("collect", 0)),
                    "wall_s": round(time.time() - t0, 2),
                    "episodes": plan.episodes()}
                wr(nm, **{nm: out[nm]})
            all_heal = out["warm_exact"] and all(
                out[nm]["exact"] and out[nm]["n_responders"] == MP_DPS
                and out[nm]["collect_entries"] >= 2
                for nm, _p in scens)
            wr("complete", all_heal=all_heal, **out)
            return 0 if all_heal else 1
        finally:
            tp.set_conn_pool(None)
            for _name, p, errlog in procs:
                p.terminate()
            for _name, p, errlog in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                errlog.close()
            if cn is not None:
                cn.stop()


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--measure-child", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--sched", action="store_true")
    ap.add_argument("--tree", action="store_true")
    ap.add_argument("--multiproc", action="store_true")
    ap.add_argument("--record-path", default=None)
    args = ap.parse_args()
    if args.measure_child:
        global _REC_PATH
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()
        _REC_PATH = args.record_path
        if args.sched:
            sys.exit(child_sched(args))
        if args.multiproc:
            sys.exit(child_multiproc(args))
        sys.exit(child_tree(args))
    sys.exit(main_parent(args))


if __name__ == "__main__":
    main()
