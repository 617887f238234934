#!/usr/bin/env python
"""Tree-roster bench: O(log n) tree overlay vs star fan-out at 16/64/256
DPs — the PR-11 headline numbers (BENCH_TREE_r01).

One supervised child per roster size (bench.py pattern: jax-free parent
survives child segfaults/timeouts; children write progressive records).
Each roster child boots an in-process TCP roster (1 CN + N DPs), warms
every kernel with the link model OFF, then installs the WAN LinkModel
(300 ms / 100 Mbps per frame) and times the same sum survey both ways
(DP reply caches primed first — see the inline note — so the timed
reps measure dispatch topology, not this one box serializing N
machines' worth of encrypts):

  star   DRYNX_TOPOLOGY=star — the root CN dials all N DPs itself
         (FAN_OUT_WORKERS-wide, so wall grows ~N/workers)
  tree   default overlay — relays fold their subtrees, the root hears
         only its forest roots' folded partials

Per mode it records surveys/s (1 / best wall) and bytes-at-root (the
LinkModel's receive ledger for the root CN, the number the tree exists
to shrink). Two more children close the loop:

  transcript    proofs-on 3-level tree (7 DPs, fanout 2) + VN trio:
                tree and star must commit byte-identical VN audit
                transcripts (range proofs ride relay hops as batched
                blobs, hop aggregation proofs parent-verified)
  multiproc-16  16 DP + 1 CN as REAL `cmd/server run` subprocesses
                (per-process DRYNX_PROOF_PLANE, like a deployment);
                the tree survey must return the exact sum of the data
                files with every DP responding

Acceptance (parent-checked): identical results tree vs star at every
roster size, tree >= 2x star surveys/s at 256 DPs, bytes-at-root
reduced by >= the fold factor (tree fanout) at 256, transcript
identity, and the multi-process deployment exact.

Usage:
  python scripts/bench_tree_rosters.py            # full -> BENCH_TREE_r01.json
  python scripts/bench_tree_rosters.py --smoke    # ~30 s check.sh tier
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

RECORD = os.path.join(ROOT, "BENCH_TREE_r01.json")

ROSTER_SIZES = [16, 64, 256]
SMOKE_DPS = 7            # fanout 2 -> a 3-level tree
DATA_SEED = 88
DP_ROWS = 8
LINK_DELAY_MS = 300.0    # the WAN point where dispatch depth is the story
LINK_MBPS = 100.0
SMOKE_DELAY_MS = 50.0
CHILD_TIMEOUT_S = 3000.0  # the transcript child compiles proof kernels
                          # cold on a cache miss; roster children are
                          # link-dominated and finish in minutes

MULTIPROC_DPS = 16


def log(msg):
    print(f"[tree-rosters] {msg}", file=sys.stderr, flush=True)


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
    # children install the LinkModel themselves AFTER warmup; topology
    # and fanout are flipped per measured survey inside the child
    for k in ("DRYNX_LINK_DELAY_MS", "DRYNX_LINK_MBPS", "DRYNX_TOPOLOGY",
              "DRYNX_TREE_FANOUT", "DRYNX_FANOUT"):
        env.pop(k, None)
    return env


def _compare(by):
    """Acceptance comparisons over the per-variant records (full mode)."""
    cmp, accept = {}, {}

    def ok(name):
        return by.get(name, {}).get("status") == "ok"

    curve = []
    results_ok = True
    for n in ROSTER_SIZES:
        name = f"roster-{n}"
        if not ok(name):
            results_ok = False
            continue
        r = by[name]
        curve.append({
            "n_dps": n, "fanout": r["fanout"], "depth": r["depth"],
            "star_surveys_per_s": r["star_surveys_per_s"],
            "tree_surveys_per_s": r["tree_surveys_per_s"],
            "star_bytes_at_root": r["star_bytes_at_root"],
            "tree_bytes_at_root": r["tree_bytes_at_root"],
            "speedup_x": round(r["star_wall_min_s"] / r["tree_wall_min_s"],
                               2),
            "root_byte_reduction_x": round(
                r["star_bytes_at_root"] / r["tree_bytes_at_root"], 1)})
        results_ok &= r["star_result_sha"] == r["tree_result_sha"]
    cmp["roster_curve"] = curve
    accept["results_identical_all_rosters"] = \
        results_ok and len(curve) == len(ROSTER_SIZES)
    if ok("roster-256"):
        r = by["roster-256"]
        cmp["speedup_at_256_x"] = round(
            r["star_wall_min_s"] / r["tree_wall_min_s"], 2)
        accept["tree_2x_star_at_256"] = cmp["speedup_at_256_x"] >= 2.0
        cmp["root_byte_reduction_at_256_x"] = round(
            r["star_bytes_at_root"] / r["tree_bytes_at_root"], 1)
        accept["root_bytes_reduced_ge_fold_factor"] = \
            cmp["root_byte_reduction_at_256_x"] >= r["fanout"]
    if ok("transcript"):
        t = by["transcript"]
        cmp["transcript_shas"] = {"tree": t["tree_transcript_sha"],
                                  "star": t["star_transcript_sha"]}
        accept["transcripts_identical"] = (
            t["tree_transcript_sha"] == t["star_transcript_sha"]
            and t["all_true"])
    else:
        accept["transcripts_identical"] = False
    if ok("multiproc-16"):
        m = by["multiproc-16"]
        accept["multiproc_exact"] = m["result_exact"] and \
            m["n_responders"] == MULTIPROC_DPS
    else:
        accept["multiproc_exact"] = False
    return cmp, accept


def main_parent(args):
    _arm_parent()
    timeout = args.timeout or (300 if args.smoke else CHILD_TIMEOUT_S)
    doc = {"round": "r01", "bench": "tree_rosters",
           "smoke": bool(args.smoke),
           "link": {"delay_ms": (SMOKE_DELAY_MS if args.smoke
                                 else LINK_DELAY_MS), "mbps": LINK_MBPS},
           "child_timeout_s": timeout, "variants": []}
    record_path = os.path.join(ROOT, ".tree_rosters_record.json")
    out = args.out or RECORD

    if args.smoke:
        plan = [("smoke", [])]
    else:
        plan = [(f"roster-{n}", ["--n-dps", str(n)]) for n in ROSTER_SIZES]
        plan += [("transcript", ["--transcript"]),
                 ("multiproc-16", ["--multiproc"])]
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
    cmp, accept = _compare(by)
    doc["comparisons"], doc["accept"] = cmp, accept
    write_progressive(out, doc)
    print(json.dumps({"comparisons": cmp, "accept": accept}), flush=True)
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


def _boot(roles, tmpdir):
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
                      db_path=os.path.join(tmpdir, f"{role}{i}.db"))
        n.start()
        entries.append(RosterEntry(name=f"{role}{i}", role=role,
                                   host=n.address[0], port=n.address[1],
                                   public=pub))
        nodes.append(n)
    return nodes, entries, datas, rng


def _share_pub_table(nodes, roster):
    """Every in-process node would otherwise build the SAME collective
    fixed-base table (~1k host bigint adds each — minutes at 256 nodes).
    One build, shared by reference: pure read-only cache priming."""
    coll = roster.collective_pub()
    tbl = nodes[0]._pub_table(coll)
    for n in nodes[1:]:
        n._tbl_cache = {coll: tbl}


def child_roster(args):
    """Tree vs star surveys/s + bytes-at-root over one roster size."""
    import tempfile

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.service import topology as topo
    from drynx_tpu.service import transport as tp
    from drynx_tpu.service.node import RemoteClient, Roster

    n_dps = args.n_dps
    delay = SMOKE_DELAY_MS if args.smoke else LINK_DELAY_MS
    reps = 2 if n_dps >= 256 else 3
    if args.smoke:
        os.environ["DRYNX_TREE_FANOUT"] = "2"   # 7 DPs -> a 3-level tree
    b = topo.tree_fanout(n_dps)
    wr("boot", n_dps=n_dps, fanout=b, depth=topo.depth(n_dps, b),
       link={"delay_ms": delay, "mbps": LINK_MBPS}, reps=reps)
    tmpdir = tempfile.mkdtemp(prefix="tree_rosters_")
    nodes, entries, datas, rng = _boot(["cn"] + ["dp"] * n_dps, tmpdir)
    roster = Roster(entries)
    _share_pub_table(nodes, roster)
    client = RemoteClient(roster, rng)
    client.broadcast_roster()
    dl = eg.DecryptionTable(limit=30000)   # 256 DPs x 8 rows x max 9
    want = int(sum(d.sum() for d in datas))

    def run(sid):
        t0 = time.time()
        res = client.run_survey("sum", query_min=0, query_max=9,
                                survey_id=sid, dlog=dl)
        rx = dict(client.last_net.get("rx_by_node") or {})
        return res, time.time() - t0, rx.get("cn0", 0)

    try:
        # -- warmup, link OFF: first kernel traces must be serial (XLA
        # CPU races on concurrent tracing), and the star root's fold
        # covers every tree fold width, so the tree warm survey below
        # re-traces nothing on concurrent relay threads
        tp.set_link_model(tp.LinkModel())
        t0 = time.time()
        with _env(DRYNX_TOPOLOGY="star", DRYNX_FANOUT="serial"):
            res, dt, _ = run("warm-star")
            assert int(res) == want
            wr("warm_star", warm_star_s=round(dt, 1))
        with _env():
            res, dt, _ = run("warm-tree")
            assert int(res) == want
            wr("warm_tree", warm_tree_s=round(dt, 1))
        wr("warm", warmup_s=round(time.time() - t0, 1))

        # -- measured: WAN link model per frame. One un-timed prime
        # survey per mode fills every DP's reply cache (the idempotent
        # survey_dp re-entry path), so timed reps replay identical
        # cached contributions: on a real roster N DPs encrypt
        # CONCURRENTLY on N machines (~one encrypt of wall), but this
        # box serializes N encrypts on one core — a ~20 s emulation
        # artifact at 256 DPs that would bury the dispatch-depth story
        # the LinkModel exists to measure. Cold walls are recorded too.
        tp.set_link_model(tp.LinkModel(delay, LINK_MBPS))
        out = {}
        for mode, env in (("star", {"DRYNX_TOPOLOGY": "star"}), ("tree", {})):
            walls, rxs, res = [], [], None
            with _env(**env):
                _, cold, _ = run(f"meas-{mode}")      # prime reply caches
                wr(f"prime_{mode}",
                   **{f"{mode}_cold_wall_s": round(cold, 3)})
                for i in range(reps):
                    res, dt, rx = run(f"meas-{mode}")
                    walls.append(round(dt, 3))
                    rxs.append(rx)
            out[mode] = (walls, rxs, res)
            wr(f"survey_{mode}",
               **{f"{mode}_wall_s": walls,
                  f"{mode}_wall_min_s": min(walls),
                  f"{mode}_surveys_per_s": round(1.0 / min(walls), 4),
                  f"{mode}_bytes_at_root": min(rxs),
                  f"{mode}_result_sha": _sha(int(res))})
        if args.smoke:
            s, t = out["star"], out["tree"]
            assert _sha(int(s[2])) == _sha(int(t[2]))     # same sum
            assert 0 < min(t[1]) < min(s[1])              # root bytes shrink
        wr("complete")
        return 0
    finally:
        tp.set_link_model(None)
        tp.set_conn_pool(None)
        for n in nodes:
            n.stop()


def child_transcript(args):
    """Proofs-on 3-level tree vs star: byte-identical VN transcripts."""
    import tempfile

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.resilience import policy as rp
    from drynx_tpu.service import transport as tp
    from drynx_tpu.service.node import RemoteClient, Roster

    wr("boot", n_dps=SMOKE_DPS, fanout=2)
    tmpdir = tempfile.mkdtemp(prefix="tree_transcript_")
    with _env(DRYNX_TREE_FANOUT="2"):
        nodes, entries, datas, rng = _boot(
            ["cn"] + ["dp"] * SMOKE_DPS + ["vn"] * 3, tmpdir)
        roster = Roster(entries)
        _share_pub_table(nodes, roster)
        client = RemoteClient(roster, rng)
        client.broadcast_roster()
        dl = eg.DecryptionTable(limit=1000)

        def run(sid):
            tp.set_conn_pool(None)
            t0 = time.time()
            res, block = client.run_survey(
                "sum", query_min=0, query_max=9, proofs=True,
                ranges=[(4, 4)], survey_id=sid, dlog=dl,
                timeout=rp.COLD_COMPILE_WAIT_S)
            norm = {k.replace(sid, "SID"): v
                    for k, v in block["bitmap"].items()}
            return int(res), norm, time.time() - t0

        try:
            res_t, tr_t, dt = run("tr-tree")
            wr("tree", tree_wall_s=round(dt, 1), tree_result=res_t,
               tree_transcript_sha=_sha(tr_t), bitmap_len=len(tr_t))
            with _env(DRYNX_TOPOLOGY="star"):
                res_s, tr_s, dt = run("tr-star")
            wr("star", star_wall_s=round(dt, 1), star_result=res_s,
               star_transcript_sha=_sha(tr_s))
            want = int(sum(d.sum() for d in datas))
            wr("complete", all_true=(set(tr_t.values()) == {1}),
               results_equal=(res_t == res_s == want))
            return 0
        finally:
            tp.set_conn_pool(None)
            for n in nodes:
                n.stop()


def child_multiproc(args):
    """A real multi-process deployment: 1 CN + 16 DPs as `cmd/server run`
    subprocesses, each with its own DRYNX_PROOF_PLANE (per-process device
    policy, like the 20-machine reference deployment). The tree survey
    must return the exact sum of the data files."""
    import socket
    import tempfile

    import numpy as np
    from drynx_tpu.cmd import toml_io
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.service.node import RemoteClient, Roster, RosterEntry

    tmpdir = tempfile.mkdtemp(prefix="tree_multiproc_")
    rng = np.random.default_rng(DATA_SEED)
    roles = ["cn"] + ["dp"] * MULTIPROC_DPS
    env = dict(os.environ)
    env["DRYNX_PROOF_PLANE"] = "off"   # per-process plane policy
    # the node processes share this child's persistent cache
    from drynx_tpu.utils.cache import ENV_VAR, enable_compilation_cache
    env[ENV_VAR] = enable_compilation_cache()
    procs, entries, datas = [], [], []
    wr("boot", n_procs=len(roles))
    try:
        for i, role in enumerate(roles):
            name = f"{role}{i}"
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            x, pub = eg.keygen(rng)
            cfg = toml_io.dumps({"node": {
                "name": name, "host": "127.0.0.1", "port": port,
                "secret": hex(x), "public_x": hex(pub[0]),
                "public_y": hex(pub[1])}})
            cmd = [sys.executable, "-m", "drynx_tpu.cmd.server", "run"]
            if role == "dp":
                data = rng.integers(0, 10, size=(DP_ROWS,)).astype(np.int64)
                datas.append(data)
                df = os.path.join(tmpdir, f"{name}.txt")
                np.savetxt(df, data, fmt="%d")
                cmd += ["--data", df]
            errlog = open(os.path.join(tmpdir, f"{name}.log"), "wb")
            p = subprocess.Popen(cmd, stdin=subprocess.PIPE, stderr=errlog,
                                 env=env, cwd=ROOT)
            p.stdin.write(cfg.encode())
            p.stdin.close()
            procs.append((name, p, errlog))
            entries.append(RosterEntry(name=name, role=role,
                                       host="127.0.0.1", port=port,
                                       public=pub))
        # wait until every server logs its listen line
        deadline = time.time() + 120
        for name, p, _ in procs:
            lp = os.path.join(tmpdir, f"{name}.log")
            while True:
                if os.path.exists(lp) and b"listening" in open(lp, "rb").read():
                    break
                if p.poll() is not None or time.time() > deadline:
                    raise RuntimeError(f"server {name} never came up")
                time.sleep(0.2)
        wr("listening")
        roster = Roster(entries)
        client = RemoteClient(roster, rng)
        client.broadcast_roster()
        dl = eg.DecryptionTable(limit=3000)
        want = int(sum(d.sum() for d in datas))
        t0 = time.time()
        res = client.run_survey("sum", query_min=0, query_max=9,
                                survey_id="mp-tree", dlog=dl)
        wr("complete", wall_s=round(time.time() - t0, 1),
           result=int(res), want=want, result_exact=(int(res) == want),
           n_responders=len(client.last_responders),
           absent=list(client.last_absent))
        return 0
    finally:
        for _name, p, errlog in procs:
            p.terminate()
        for _name, p, errlog in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
            errlog.close()


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument("--measure-child", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--n-dps", type=int, default=SMOKE_DPS)
    ap.add_argument("--transcript", action="store_true")
    ap.add_argument("--multiproc", action="store_true")
    ap.add_argument("--record-path", default=None)
    args = ap.parse_args()
    if args.measure_child:
        global _REC_PATH
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()
        _REC_PATH = args.record_path
        if args.transcript:
            sys.exit(child_transcript(args))
        if args.multiproc:
            sys.exit(child_multiproc(args))
        sys.exit(child_roster(args))
    sys.exit(main_parent(args))


if __name__ == "__main__":
    main()
