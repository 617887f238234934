"""PR-10 network plane: binary v2 wire frames, pooled connections,
concurrent fan-out, and pool-backed remote CNs.

Covers the four layers together because their contracts interlock:
the v2 wire must roundtrip every handler payload value-identically, the
ConnPool must never hand out a socket desynced by a timed-out call, the
fan-out must keep results roster-ordered so survey sums and VN
transcripts stay byte-identical to the old serial loops, and a remote
CN holding a warm CryptoPool must consume DRO slabs instead of
precomputing (ROADMAP item 5's remaining gap).
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

from drynx_tpu.proofs import requests as rq
from drynx_tpu.resilience import policy as rp
from drynx_tpu.resilience.faults import FaultPlan, FaultSpec, set_fault_plan
from drynx_tpu.service import transport as tp
from drynx_tpu.service.node import (DrynxNode, RemoteClient, Roster,
                                    RosterEntry, call_entry, fan_out)
from drynx_tpu.service.transport import (CallTimeout, Conn, ConnPool,
                                         LinkModel, NodeServer,
                                         decode_frame, encode_frame,
                                         jsonable, pack_array,
                                         set_conn_pool, unb64, unpack_array)


@pytest.fixture(autouse=True)
def _clean_process_globals():
    """Transport and pool state is process-global by design; tests must
    not leak negotiated sockets, fault plans, or an active CryptoPool
    into each other."""
    from drynx_tpu import pool as pool_mod

    set_fault_plan(None)
    set_conn_pool(None)
    pool_mod.activate(None)
    yield
    set_fault_plan(None)
    set_conn_pool(None)
    pool_mod.activate(None)


def _listify(o):
    """Tuples arrive as JSON lists on either wire; normalize for
    equality checks against the decoded tree."""
    if isinstance(o, dict):
        return {k: _listify(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_listify(v) for v in o]
    return o


def _pack_bytes(b: bytes) -> dict:
    return pack_array(np.frombuffer(b, dtype=np.uint8))


def _handler_payloads() -> dict:
    """One representative message per handler payload family in
    service/node.py — every shape the wire must carry."""
    rng = np.random.default_rng(7)
    cts = rng.integers(0, 2 ** 16, size=(4, 2, 3, 16)).astype(np.uint32)
    pts = rng.integers(0, 2 ** 16, size=(4, 3, 16)).astype(np.uint32)
    A = rng.integers(0, 2 ** 16, size=(2, 16, 3, 2, 16)).astype(np.uint32)
    roster = Roster([RosterEntry(name="cn0", role="cn", host="127.0.0.1",
                                 port=7000, public=(12345, 67890))])
    return {
        "set_roster": {"type": "set_roster", "roster": roster.to_dict()},
        "survey_query": {
            "type": "survey_query", "op": "sum", "survey_id": "s1",
            "query_min": 0, "query_max": 9, "proofs": True,
            "ranges": [[16, 4], [16, 4]], "obfuscation": False,
            "diffp": {"noise_list_size": 8, "lap_mean": 0.0,
                      "lap_scale": 2.0, "quanta": 1.0, "scale": 1.0,
                      "limit": 4.0},
            "lr_params": None, "group_by": None, "range_offset": 0,
            "min_dp_quorum": 0, "dp_exclude": [],
            "client_pub": [12345, 67890]},
        "survey_dp": {
            "type": "survey_dp", "op": "sum", "survey_id": "s1",
            "query_min": 0, "query_max": 9, "range_offset": 0,
            "proofs": True, "ranges": [[16, 4]],
            # the nested range_sigs blob: publics per CN + stacked A tables
            "range_sigs": {"16": {"pubs": [[1, 2], [3, 4]],
                                  "A": pack_array(A)}}},
        "survey_dp_reply": {"type": "survey_dp_reply",
                            "cts": pack_array(cts)},
        "range_sig_reply": {"type": "range_sig_reply", "pub": [111, 222],
                            "A": pack_array(A[0])},
        "contrib": {"type": "shuffle_contrib", "survey_id": "s1",
                    "proofs": False, "cts": pack_array(cts)},
        "ks_contrib": {"type": "ks_contrib", "survey_id": "s1",
                       "proofs": False, "client_pub": [12345, 67890],
                       "k_component": pack_array(pts)},
        "ks_reply": {"type": "ks_contrib_reply", "u": pack_array(pts),
                     "w": pack_array(pts)},
        "proof_request": {
            "type": "proof_request", "proof_type": "range",
            "survey_id": "s1", "sender_id": "dp0",
            "differ_info": "range-dp0", "round_id": 0,
            "data": _pack_bytes(b"\x00\x01\xfe\xff" * 64),
            "signature": _pack_bytes(b"\x80" * 96)},
        "end_verification_reply": {
            "type": "end_verification_reply", "block_index": 1,
            "block_hash": "ab" * 32,
            "bitmap": {"vn0:range-dp0": "BM_TRUE", "vn1:ks-cn0": "BM_TRUE"},
            "vn_reported": ["vn0", "vn1"], "vn_absent": []},
        "get_proofs_reply": {
            "type": "get_proofs_reply",
            "proofs": {"range-dp0": _pack_bytes(b"\x01\x02" * 100)}},
    }


# ---------------------------------------------------------------------------
# v2 wire format
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_handler_payloads()))
def test_v2_roundtrip_every_handler_payload(name):
    """encode/decode under v2 returns the identical value tree (bytes stay
    bytes); v1 returns the jsonable() form (bytes as base64); packed
    arrays reconstruct bit-identically under both wires."""
    msg = _handler_payloads()[name]
    f2 = encode_frame(msg, 2)
    dec2 = decode_frame(f2[4:], 2)
    assert dec2 == _listify(msg)
    f1 = encode_frame(msg, 1)
    dec1 = decode_frame(f1[4:], 1)
    assert dec1 == jsonable(msg)

    def arrays(tree, out):
        if isinstance(tree, dict):
            if set(tree) >= {"dtype", "shape", "data"}:
                out.append(tree)
            else:
                for v in tree.values():
                    arrays(v, out)
        elif isinstance(tree, list):
            for v in tree:
                arrays(v, out)
        return out

    for a2, a1, a0 in zip(arrays(dec2, []), arrays(dec1, []),
                          arrays(msg, [])):
        want = unpack_array(a0)
        assert np.array_equal(unpack_array(a2), want)
        assert np.array_equal(unpack_array(a1), want)


def test_v2_frames_beat_v1_on_tensor_payloads():
    """Base64 inflates tensor payloads ~33%; v2 ships raw segments, so a
    ciphertext frame must come in >=20% smaller (the bench asserts the
    25% end-to-end bar over a whole survey)."""
    msg = _handler_payloads()["survey_dp_reply"]
    v1, v2 = len(encode_frame(msg, 1)), len(encode_frame(msg, 2))
    assert v2 < 0.8 * v1
    # tiny control messages may not shrink, but must stay comparable
    ping = {"type": "ping"}
    assert len(encode_frame(ping, 2)) <= len(encode_frame(ping, 1)) + 16


def test_v2_decode_rejects_garbage():
    from drynx_tpu.service.transport import CorruptFrame

    good = encode_frame({"a": b"xy"}, 2)[4:]
    for bad in (b"", b"\x00" * 6, b"\xff" + good[1:], good[:-1]):
        with pytest.raises(CorruptFrame):
            decode_frame(bad, 2)
    assert unb64(b"raw") == b"raw" and unb64("cmF3") == b"raw"


# ---------------------------------------------------------------------------
# wire negotiation
# ---------------------------------------------------------------------------

def test_wire_negotiation_default_v2_and_kill_switch(monkeypatch):
    srv = NodeServer()
    srv.register("echo", lambda m: {"blob": m["blob"]})
    srv.start()
    try:
        c = Conn(srv.host, srv.port)
        assert c.wire == 2
        r = c.call({"type": "echo", "blob": b"\x00\xff" * 8})
        assert r["blob"] == b"\x00\xff" * 8      # raw bytes end to end
        c.close()

        monkeypatch.setenv("DRYNX_WIRE", "json")
        c1 = Conn(srv.host, srv.port)
        assert c1.wire == 1
        r1 = c1.call({"type": "echo", "blob": b"\x00\xff" * 8})
        assert unb64(r1["blob"]) == b"\x00\xff" * 8   # base64 on v1
        c1.close()
    finally:
        srv.stop()


def test_wire_negotiation_old_server_stays_v1():
    """A pre-v2 server has no wire_hello handling and replies with a
    handler error; the client must stay on v1 and keep working."""
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)

    def serve():
        conn, _ = lsock.accept()
        with conn:
            while True:
                msg = tp.recv_msg(conn)
                if msg is None:
                    return
                if msg.get("type") == "wire_hello":
                    tp.send_msg(conn, {"type": "error",
                                       "error": "no handler for "
                                                "'wire_hello'"})
                else:
                    tp.send_msg(conn, {"type": "echo_reply",
                                       "v": msg["v"]})

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        c = Conn(*lsock.getsockname())
        assert c.wire == 1
        assert c.call({"type": "echo", "v": 42})["v"] == 42
        c.close()
    finally:
        lsock.close()


# ---------------------------------------------------------------------------
# connection pool
# ---------------------------------------------------------------------------

def _echo_server():
    srv = NodeServer()
    srv.register("echo", lambda m: {"v": m["v"]})
    srv.register("slow", lambda m: (time.sleep(0.6), {"ok": True})[1])
    srv.start()
    return srv


def test_conn_pool_reuses_and_bounds_idle():
    srv = _echo_server()
    pool = ConnPool(max_idle=2)
    try:
        c1 = pool.get(srv.host, srv.port, peer="s")
        assert c1.call({"type": "echo", "v": 1})["v"] == 1
        pool.put(c1)
        c2 = pool.get(srv.host, srv.port, peer="s")
        assert c2 is c1                       # reused, not re-dialed
        st = pool.stats()
        assert st["connects"] == 1 and st["reuses"] == 1
        extra = [pool.get(srv.host, srv.port, peer="s") for _ in range(3)]
        for c in [c2] + extra:
            pool.put(c)
        assert pool.idle_count() == 2         # bounded at max_idle
        pool.close_all()
        assert pool.idle_count() == 0
    finally:
        srv.stop()


def test_conn_pool_global_cap_evicts_lru(monkeypatch):
    """max_total bounds idle sockets across ALL keys: at the cap a put
    closes the globally least-recently-pooled connection first, so a
    256-peer roster's cold sockets age out while warm ones survive.
    DRYNX_CONN_POOL_MAX overrides the policy default per process."""
    s1, s2 = _echo_server(), _echo_server()
    pool = ConnPool(max_idle=4, max_total=2)
    try:
        a1 = pool.get(s1.host, s1.port, peer="a")
        a2 = pool.get(s1.host, s1.port, peer="a")
        b1 = pool.get(s2.host, s2.port, peer="b")
        pool.put(a1)                     # oldest stamp -> LRU victim
        pool.put(b1)
        assert pool.idle_count() == 2 and pool.stats()["evictions"] == 0
        pool.put(a2)                     # at cap: a1 ages out, b1 stays
        st = pool.stats()
        assert st["evictions"] == 1 and st["idle"] == 2
        assert a1.closed and not b1.closed
        assert pool.get(s1.host, s1.port, peer="a") is a2
        assert pool.get(s2.host, s2.port, peer="b") is b1
        pool.close_all()
    finally:
        s1.stop()
        s2.stop()
    monkeypatch.setenv("DRYNX_CONN_POOL_MAX", "3")
    assert ConnPool().max_total == 3
    monkeypatch.delenv("DRYNX_CONN_POOL_MAX")
    assert ConnPool().max_total == rp.CONN_POOL_MAX


def test_conn_pool_never_reuses_timed_out_conn():
    """The half-read bugfix: a CallTimeout leaves the reply in flight; the
    broken conn must never be pooled, and the next checkout must get a
    FRESH socket that answers the new request (not the stale reply)."""
    srv = _echo_server()
    pool = ConnPool()
    try:
        c = pool.get(srv.host, srv.port, timeout=0.1, peer="s")
        with pytest.raises(CallTimeout):
            c.call({"type": "slow"})
        assert c.broken
        pool.put(c)                           # refused: discarded
        assert pool.idle_count() == 0
        c2 = pool.get(srv.host, srv.port, timeout=5.0, peer="s")
        assert c2 is not c
        assert c2.call({"type": "echo", "v": 7})["v"] == 7
        pool.put(c2)
    finally:
        srv.stop()


def test_conn_pool_health_check_discards_desynced_socket():
    """A pooled socket with buffered bytes (a reply that landed after its
    caller gave up without breaking the conn) fails the MSG_PEEK health
    check on checkout."""
    srv = _echo_server()
    pool = ConnPool()
    try:
        c = pool.get(srv.host, srv.port, peer="s")
        assert c.call({"type": "echo", "v": 0})["v"] == 0
        # push a request and abandon the reply: conn not broken, but the
        # socket now holds a stale frame
        tp.send_frame(c.sock, {"type": "echo", "v": 1}, c.wire)
        deadline = time.time() + 5.0
        while time.time() < deadline:       # wait for the reply to buffer
            try:
                c.sock.setblocking(False)
                c.sock.recv(1, socket.MSG_PEEK)
                break
            except BlockingIOError:
                time.sleep(0.01)
            finally:
                c.sock.settimeout(5.0)
        pool.put(c)
        assert pool.idle_count() == 1
        c2 = pool.get(srv.host, srv.port, peer="s")
        assert c2 is not c                    # desynced one was discarded
        assert pool.stats()["discards"] >= 1
        assert c2.call({"type": "echo", "v": 9})["v"] == 9
        pool.put(c2)
    finally:
        srv.stop()


def test_conn_pool_purges_suspect_peer_stack_on_redial():
    """PR-17 satellite: a conn that breaks mid-exchange marks its peer
    suspect. The peer's remaining pooled sockets can still pass MSG_PEEK
    (a cut link never delivers a FIN), so a suspect key must bypass its
    idle stack — and once a FRESH dial succeeds (the peer is
    demonstrably back), the stale stack is purged rather than handed
    out to burn one call timeout each."""
    srv = _echo_server()
    pool = ConnPool(max_idle=4)
    try:
        a = pool.get(srv.host, srv.port, peer="s")
        b = pool.get(srv.host, srv.port, peer="s")
        c = pool.get(srv.host, srv.port, peer="s")
        for x in (a, b):
            assert x.call({"type": "echo", "v": 0})["v"] == 0
            pool.put(x)
        assert pool.idle_count() == 2
        # c breaks mid-exchange (timeout on a slow handler): peer suspect
        c._timeout = 0.1
        c.sock.settimeout(0.1)
        with pytest.raises(CallTimeout):
            c.call({"type": "slow"})
        pool.discard(c)
        # a and b still sit idle and still look healthy — but the next
        # checkout must NOT trust them: fresh dial, stale stack purged
        d = pool.get(srv.host, srv.port, peer="s")
        assert d is not a and d is not b
        st = pool.stats()
        assert st["purges"] == 2 and pool.idle_count() == 0
        assert a.closed and b.closed
        assert d.call({"type": "echo", "v": 5})["v"] == 5
        pool.put(d)
        # suspicion cleared: the pooled socket is trusted again
        assert pool.get(srv.host, srv.port, peer="s") is d
        pool.close_all()
    finally:
        srv.stop()


def test_conn_pool_overflow_close_does_not_condemn_peer():
    """Idle-depth overflow closes a healthy surplus conn; that must not
    mark the peer suspect (no purge storm on a busy healthy peer)."""
    srv = _echo_server()
    pool = ConnPool(max_idle=1)
    try:
        a = pool.get(srv.host, srv.port, peer="s")
        b = pool.get(srv.host, srv.port, peer="s")
        pool.put(a)
        pool.put(b)            # overflow: closed, NOT suspect
        assert pool.idle_count() == 1
        c = pool.get(srv.host, srv.port, peer="s")
        assert c is a          # the pooled socket is still trusted
        assert pool.stats()["purges"] == 0
        pool.put(c)
        pool.close_all()
    finally:
        srv.stop()


def test_call_entry_checks_out_of_process_pool():
    srv = _echo_server()
    try:
        pool = ConnPool()
        set_conn_pool(pool)
        e = RosterEntry(name="s", role="cn", host=srv.host, port=srv.port,
                        public=(0, 0))
        for v in range(3):
            assert call_entry(e, {"type": "echo", "v": v})["v"] == v
        st = pool.stats()
        assert st["connects"] == 1 and st["reuses"] == 2
    finally:
        set_conn_pool(None)
        srv.stop()


# ---------------------------------------------------------------------------
# concurrent fan-out
# ---------------------------------------------------------------------------

def test_fan_out_results_stay_roster_ordered():
    entries = [RosterEntry(name=f"n{i}", role="dp", host="x", port=i,
                           public=(0, 0)) for i in range(6)]

    def call(e, m):
        # later roster entries answer FIRST: completion order is the
        # reverse of roster order, results must not be
        time.sleep((len(entries) - e.port) * 0.02)
        if e.port == 3:
            raise OSError("down")
        return {"who": e.name, "echo": m["k"]}

    outs = fan_out(entries, lambda e: {"k": e.port * 10}, call=call)
    assert len(outs) == 6
    for i, (r, err) in enumerate(outs):
        if i == 3:
            assert r is None and isinstance(err, OSError)
        else:
            assert err is None
            assert r == {"who": f"n{i}", "echo": i * 10}


def test_fan_out_serial_env_matches_parallel(monkeypatch):
    entries = [RosterEntry(name=f"n{i}", role="dp", host="x", port=i,
                           public=(0, 0)) for i in range(4)]

    def call(e, m):
        return e.port * 2

    par = fan_out(entries, lambda e: {}, call=call)
    monkeypatch.setenv("DRYNX_FANOUT", "serial")
    ser = fan_out(entries, lambda e: {}, call=call)
    assert par == ser == [(0, None), (2, None), (4, None), (6, None)]
    monkeypatch.setenv("DRYNX_FANOUT_WORKERS", "2")
    monkeypatch.delenv("DRYNX_FANOUT")
    assert fan_out(entries, lambda e: {}, call=call) == par


def test_fan_out_overlaps_link_latency():
    """The point of the tentpole: n concurrent calls over a latency-bound
    link cost ~max, not ~sum."""
    entries = [RosterEntry(name=f"n{i}", role="dp", host="x", port=i,
                           public=(0, 0)) for i in range(6)]

    def call(e, m):
        time.sleep(0.1)
        return e.name

    t0 = time.perf_counter()
    outs = fan_out(entries, lambda e: {}, call=call, workers=6)
    par = time.perf_counter() - t0
    assert [r for r, _ in outs] == [e.name for e in entries]
    t0 = time.perf_counter()
    fan_out(entries, lambda e: {}, call=call, workers=1)
    ser = time.perf_counter() - t0
    assert par < ser / 2           # 6x0.1s serial vs ~0.1s overlapped


# ---------------------------------------------------------------------------
# fault-plan determinism + link accounting under concurrency
# ---------------------------------------------------------------------------

def test_fault_plan_draws_are_arrival_order_independent():
    """Per-(spec, target, seq) keyed draws: the verdict map over (target,
    event#) must be identical whether events arrive serially in roster
    order or interleaved across threads in reverse."""
    targets = [f"dp{i}" for i in range(5)]
    events = 8

    def specs():
        return [FaultSpec(where="connect", kind="refuse", target="dp*",
                          prob=0.5),
                FaultSpec(where="request", kind="drop", target="dp*",
                          mtype="survey_dp", prob=0.4)]

    serial = FaultPlan(seed=11, specs=specs())
    want = {}
    for t in targets:
        for k in range(events):
            want[("connect", t, k)] = serial.pick("connect", t) is not None
            want[("request", t, k)] = (
                serial.pick("request", t, "survey_dp") is not None)

    threaded = FaultPlan(seed=11, specs=specs())
    got = {}
    lock = threading.Lock()

    def worker(t):
        for k in range(events):
            a = threaded.pick("connect", t) is not None
            b = threaded.pick("request", t, "survey_dp") is not None
            with lock:
                got[("connect", t, k)] = a
                got[("request", t, k)] = b

    threads = [threading.Thread(target=worker, args=(t,))
               for t in reversed(targets)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert got == want


def test_fault_plan_count_caps_are_per_target():
    """A count cap must be a per-(spec, target) budget, not a global one a
    fast thread can drain from under the others."""
    plan = FaultPlan(seed=0, specs=[FaultSpec(where="connect", kind="refuse",
                                              target="dp*", prob=1.0,
                                              count=2)])
    fired = {t: sum(plan.pick("connect", t) is not None for _ in range(5))
             for t in ("dp0", "dp1", "dp2")}
    assert fired == {"dp0": 2, "dp1": 2, "dp2": 2}
    assert plan.specs[0].fired == 6


def test_link_model_concurrent_charges_account_exactly():
    m = LinkModel()          # no delay: pure accounting
    threads = [threading.Thread(
        target=lambda i=i: [m.charge(3, peer=f"p{i % 2}")
                            for _ in range(200)]) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = m.stats()
    assert st["bytes_total"] == 8 * 200 * 3
    assert st["msgs_total"] == 8 * 200
    assert st["by_peer"] == {"p0": 2400, "p1": 2400}
    m.reset_stats()
    assert m.stats() == {"bytes_total": 0, "msgs_total": 0, "by_peer": {}}


# ---------------------------------------------------------------------------
# end-to-end: parallel == serial, v2 < v1, pooled conns
# ---------------------------------------------------------------------------

def _boot_roster(tmp_path, roles, seed=21):
    rng = np.random.default_rng(seed)
    nodes, entries, datas = [], [], []
    for i, role in enumerate(roles):
        x, pub = eg_keygen(rng)
        data = None
        if role == "dp":
            data = rng.integers(0, 10, size=(8,)).astype(np.int64)
            datas.append(data)
        n = DrynxNode(f"{role}{i}", x, pub, data=data,
                      db_path=str(tmp_path / f"{role}{i}.db"))
        n.start()
        entries.append(RosterEntry(name=f"{role}{i}", role=role,
                                   host=n.address[0], port=n.address[1],
                                   public=pub))
        nodes.append(n)
    return nodes, entries, datas, rng


def eg_keygen(rng):
    from drynx_tpu.crypto import elgamal as eg

    return eg.keygen(rng)


def test_survey_parallel_serial_v1_v2_pooled_all_agree(tmp_path,
                                                       monkeypatch):
    """One roster, four wire/dispatch variants of the same sum survey:
    serial-v2, parallel-v2, parallel-v1, parallel-v2-pooled. All four
    must return the exact sum with the same responder list; serial and
    parallel (pool off) must account byte-identical traffic; v1 must
    cost strictly more bytes than v2; the pooled run must reuse sockets."""
    from drynx_tpu.crypto import elgamal as eg

    nodes, entries, datas, rng = _boot_roster(
        tmp_path, ["cn", "cn", "dp", "dp", "dp"])
    roster = Roster(entries)
    client = RemoteClient(roster, rng)
    client.broadcast_roster()
    # frequency_count: 10 outputs -> real tensor payloads on the wire
    # (a 1-value sum survey is all JSON header, no byte-saving signal)
    want = {v: int(c) for v, c in
            enumerate(np.bincount(np.concatenate(datas), minlength=10))}
    dl = eg.DecryptionTable(limit=500)

    def run(sid):
        set_conn_pool(None)   # fresh sockets: each variant negotiates anew
        r = client.run_survey("frequency_count", query_min=0, query_max=9,
                              survey_id=sid, dlog=dl)
        return r, dict(client.last_net), list(client.last_responders)

    try:
        # pool off for the byte-identity pair: every call dials fresh, so
        # serial and parallel runs exchange the same frame multiset
        monkeypatch.setenv("DRYNX_CONN_POOL", "off")
        monkeypatch.setenv("DRYNX_FANOUT", "serial")
        res_ser, net_ser, resp_ser = run("sv-ser")    # also warms compiles
        monkeypatch.delenv("DRYNX_FANOUT")
        res_par, net_par, resp_par = run("sv-par")
        monkeypatch.setenv("DRYNX_WIRE", "json")
        res_v1, net_v1, resp_v1 = run("sv-v1")
        monkeypatch.delenv("DRYNX_WIRE")
        monkeypatch.delenv("DRYNX_CONN_POOL")
        res_pool, _net_pool, resp_pool = run("sv-pool")
        # second survey over the SAME pool: every peer was dialed once
        # already, so this run must ride reused sockets (no reconnects,
        # no wire hellos)
        res_pool2 = client.run_survey("frequency_count", query_min=0,
                                      query_max=9, survey_id="sv-pool2",
                                      dlog=dl)
        net_pool2 = dict(client.last_net)
        pool_stats = tp.conn_pool().stats()
    finally:
        set_conn_pool(None)
        for n in nodes:
            n.stop()

    for res in (res_ser, res_par, res_v1, res_pool, res_pool2):
        assert {int(k): int(v) for k, v in res.items()} == want
    assert resp_ser == resp_par == resp_v1 == resp_pool \
        == ["dp2", "dp3", "dp4"]
    # dispatch order must not change what crosses the wire
    assert net_ser["bytes_total"] == net_par["bytes_total"]
    assert net_ser["msgs_total"] == net_par["msgs_total"]
    assert net_ser["by_peer"] == net_par["by_peer"]
    # binary frames: the same survey costs >=25% fewer bytes than JSON
    assert net_par["bytes_total"] < 0.75 * net_v1["bytes_total"]
    # per-peer accounting is surfaced per survey: every dialed node shows
    assert {"cn0", "dp2", "dp3", "dp4"} <= set(net_par["by_peer"])
    # warm pool: the second pooled survey reuses sockets and skips the
    # per-connection hello traffic the unpooled variant pays
    assert pool_stats["reuses"] > 0
    assert net_pool2["bytes_total"] < net_par["bytes_total"]


@pytest.mark.slow
@pytest.mark.parametrize("switch,off", [
    ("DRYNX_FANOUT", "serial"),
    # the wire->device decode and the async shard pipeline (the conftest's
    # 8 devices shard the proof plane): only the host glue may move
    ("DRYNX_DEVICE_DECODE", "off"),
    ("DRYNX_ASYNC_DISPATCH", "serial")])
def test_survey_transcripts_parallel_vs_serial_identical(tmp_path,
                                                         monkeypatch,
                                                         switch, off):
    """Proofs-on: the committed VN audit bitmap (keys + verdict codes)
    must be byte-identical with a kill-switch thrown and with the default
    path — serial against parallel dispatch (the fan-out may reorder
    arrivals, never the transcript), host against device decode, blocking
    against pipelined shards."""
    from drynx_tpu.crypto import elgamal as eg

    nodes, entries, datas, rng = _boot_roster(
        tmp_path, ["cn", "cn", "dp", "vn", "vn"], seed=33)
    roster = Roster(entries)
    client = RemoteClient(roster, rng)
    client.broadcast_roster()
    dl = eg.DecryptionTable(limit=500)

    def run(sid):
        set_conn_pool(None)
        result, block = client.run_survey(
            "sum", query_min=0, query_max=9, proofs=True, ranges=[(4, 4)],
            survey_id=sid, dlog=dl, timeout=2400.0)

        def norm(bm):
            # strip the per-survey id so serial/parallel keys align
            return {k.replace(sid, "SID"): v for k, v in bm.items()}

        return result, json.dumps(norm(block["bitmap"]), sort_keys=True)

    try:
        monkeypatch.setenv(switch, off)
        res_ser, tr_ser = run("tr-ser")
        monkeypatch.delenv(switch)
        res_par, tr_par = run("tr-par")
    finally:
        set_conn_pool(None)
        for n in nodes:
            n.stop()

    assert res_ser == res_par == int(sum(d.sum() for d in datas))
    assert tr_ser == tr_par
    bm = json.loads(tr_par)
    assert bm and set(bm.values()) == {rq.BM_TRUE}


# ---------------------------------------------------------------------------
# pool-backed remote CNs (ROADMAP item 5, remaining gap)
# ---------------------------------------------------------------------------

def test_remote_cn_shuffle_consumes_pooled_dro(tmp_path):
    """A DrynxNode constructed with a warm CryptoPool serves
    shuffle_contrib from DRO slabs: zero fresh precompute, exactly
    dro_need elements consumed, and the noise multiset survives."""
    import jax
    import jax.numpy as jnp

    from drynx_tpu import pool as pool_mod
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import dro
    from drynx_tpu.pool import replenish

    rng = np.random.default_rng(5)
    x, pub = eg.keygen(rng)
    need = 8
    pool = pool_mod.CryptoPool(str(tmp_path), slab_elems=need)
    node = DrynxNode("cn0", x, pub, db_path=str(tmp_path / "cn0.db"),
                     pool=pool)
    node.start()
    try:
        node.roster = Roster([RosterEntry(name="cn0", role="cn",
                                          host="127.0.0.1", port=0,
                                          public=pub)])
        tbl = node._pub_table(node.roster.collective_pub())
        replenish.refill_to(pool, jax.random.PRNGKey(1), tbl.table, need)

        noise = np.array([0, 1, -1, 2, -2, 0, 1, -1], dtype=np.int64)
        cts = dro.encrypt_noise(jax.random.PRNGKey(2), tbl, noise)
        before = dro.PRECOMPUTE_CALLS
        r = node._h_shuffle_contrib({"type": "shuffle_contrib",
                                     "survey_id": "s", "proofs": False,
                                     "cts": pack_array(np.asarray(cts))})
        assert dro.PRECOMPUTE_CALLS == before      # pooled: no fresh build
        assert pool.counters["elements_consumed"] == need

        out = jnp.asarray(unpack_array(r["cts"]))
        vals, found = eg.decrypt_ints(out, x, eg.DecryptionTable(limit=8))
        assert bool(np.all(np.asarray(found)))
        assert np.array_equal(np.sort(np.asarray(vals)), np.sort(noise))

        # drained pool: the same handler falls back to one fresh precompute
        before = dro.PRECOMPUTE_CALLS
        node._h_shuffle_contrib({"type": "shuffle_contrib",
                                 "survey_id": "s2", "proofs": False,
                                 "cts": pack_array(np.asarray(cts))})
        assert dro.PRECOMPUTE_CALLS == before + 1
    finally:
        node.stop()


def test_remote_diffp_survey_runs_on_pooled_dro(tmp_path, monkeypatch):
    """End-to-end TCP diffp survey with pool-holding CN processes: the
    whole shuffle chain consumes slabs (PRECOMPUTE_CALLS flat,
    elements_consumed == per-CN need x n_cns) and the noisy sum stays
    within the configured limit."""
    import jax

    from drynx_tpu import pool as pool_mod
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import dro
    from drynx_tpu.pool import replenish

    S = 8
    pool = pool_mod.CryptoPool(str(tmp_path / "pool"), slab_elems=S)
    rng = np.random.default_rng(9)
    nodes, entries, datas = [], [], []
    for i, role in enumerate(["cn", "cn", "dp", "dp"]):
        x, pub = eg.keygen(rng)
        data = None
        if role == "dp":
            data = np.arange(4, dtype=np.int64)
            datas.append(data)
        n = DrynxNode(f"{role}{i}", x, pub, data=data,
                      db_path=str(tmp_path / f"{role}{i}.db"),
                      pool=pool if role == "cn" else None)
        n.start()
        entries.append(RosterEntry(name=f"{role}{i}", role=role,
                                   host=n.address[0], port=n.address[1],
                                   public=pub))
        nodes.append(n)
    roster = Roster(entries)
    client = RemoteClient(roster, rng)
    client.broadcast_roster()

    coll_tbl = eg.pub_table(roster.collective_pub())
    replenish.refill_to(pool, jax.random.PRNGKey(3), coll_tbl.table,
                        S * 2)                       # one slab per CN
    diffp = {"noise_list_size": S, "lap_mean": 0.0, "lap_scale": 2.0,
             "quanta": 1.0, "scale": 1.0, "limit": 4.0}
    before = dro.PRECOMPUTE_CALLS
    try:
        res = client.run_survey("sum", query_min=0, query_max=5,
                                survey_id="sv-diffp", diffp=diffp,
                                dlog=eg.DecryptionTable(limit=2000))
    finally:
        set_conn_pool(None)
        for n in nodes:
            n.stop()
    assert dro.PRECOMPUTE_CALLS == before            # fully pooled
    assert pool.counters["elements_consumed"] == S * 2
    want = int(sum(d.sum() for d in datas))
    assert abs(res - want) <= diffp["limit"]
