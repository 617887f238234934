"""PR-11 tree-topology rosters (drynx_tpu/service/topology.py).

The tree overlay replaces the root CN's O(n) star fan-in with O(log n)
relay hops, and its correctness rests on one algebraic contract: the
ciphertext group is abelian mod p, so ANY fold grouping yields the same
group element, and canon_points collapses every projective representative
of that element to identical bytes. This file proves the contract at
three levels — pure layout math, device folds, and full surveys over
real sockets (tree vs star must agree on results, responder sets, and VN
proof transcripts) — plus the PR's satellites: relay-failure isolation
at depth and idempotent survey_dp re-entry.
"""
import dataclasses
import json
import threading

import numpy as np
import pytest

from drynx_tpu.resilience import policy as rp
from drynx_tpu.resilience.faults import FaultPlan, set_fault_plan
from drynx_tpu.service import topology as topo
from drynx_tpu.service.node import (DrynxNode, RemoteClient, Roster,
                                    RosterEntry)
from drynx_tpu.service.transport import set_conn_pool, unpack_array


@pytest.fixture(autouse=True)
def _clean_process_globals():
    set_fault_plan(None)
    set_conn_pool(None)
    yield
    set_fault_plan(None)
    set_conn_pool(None)


# -- layout math (pure python, no jax) --------------------------------------

@pytest.mark.parametrize("n,b", [(1, 1), (2, 2), (5, 2), (10, 2),
                                 (16, 4), (37, 5), (256, 8)])
def test_tree_layout_partitions_roster(n, b):
    """The forest roots' subtrees partition the index space exactly, and
    children/parent are mutual inverses — every index is dispatched once
    whatever level it sits at."""
    seen = [j for i in topo.roots(n, b) for j in topo.subtree(i, n, b)]
    assert sorted(seen) == list(range(n))
    assert len(seen) == n                      # no index reached twice
    for j in range(n):
        p = topo.parent(j, b)
        if p is None:
            assert j in topo.roots(n, b)
        else:
            assert j in topo.children(p, n, b)
    d = topo.depth(n, b)
    assert d >= 1 and (n <= b) == (d == 1)


def test_survivor_layout_reparents_dead_relay_subtree():
    """PR 17 failover: dropping a dead interior relay from the roster
    re-derives a valid tree over the survivors — its former descendants
    land under live parents, the layout still partitions the index
    space, and the result depends only on WHO survived (roster order),
    never on probe return order."""
    order = [f"dp{i}" for i in range(10)]
    b = topo.tree_fanout(10)                       # 4: dp1 is interior
    assert topo.children(1, 10, b)                 # it really has a subtree
    alive = [n for n in order if n != "dp1"]
    layout = topo.survivor_layout(order, set(alive))
    assert layout == alive                         # roster order kept
    # probe order must not matter
    assert topo.survivor_layout(order, reversed(alive)) == layout
    # the re-derived tree over the survivors is a full partition again
    n2, b2 = len(layout), topo.tree_fanout(len(layout))
    seen = [j for i in topo.roots(n2, b2)
            for j in topo.subtree(i, n2, b2)]
    assert sorted(seen) == list(range(n2))
    assert topo.survivor_layout(order, set()) == []
    assert topo.survivor_layout(order, order) == order


def test_tree_fanout_auto_clamps_and_env(monkeypatch):
    monkeypatch.delenv(topo.ENV_FANOUT, raising=False)
    assert topo.tree_fanout(0) == 1 and topo.tree_fanout(1) == 1
    assert topo.tree_fanout(4) == rp.TREE_FANOUT_MIN
    assert topo.tree_fanout(9) == 3            # ceil(sqrt(9))
    assert topo.tree_fanout(256) == rp.TREE_FANOUT_MAX  # 16 clamped to 8
    monkeypatch.setenv(topo.ENV_FANOUT, "5")
    assert topo.tree_fanout(256) == 5
    monkeypatch.setenv(topo.ENV_FANOUT, "0")
    assert topo.tree_fanout(256) == 1          # floor at 1, never 0


def test_topology_mode_kill_switch(monkeypatch):
    monkeypatch.delenv(topo.ENV_TOPOLOGY, raising=False)
    assert topo.topology_mode() == "tree"
    monkeypatch.setenv(topo.ENV_TOPOLOGY, " STAR ")
    assert topo.topology_mode() == "star"
    monkeypatch.setenv(topo.ENV_TOPOLOGY, "ring")   # typo degrades to
    assert topo.topology_mode() == "tree"           # the default


# -- canonical folds: the mod-p associativity contract ----------------------

def _random_ct_stack(k: int, v: int, seed: int) -> np.ndarray:
    """(k, V, 2, 3, 16) stack of REAL curve points (fixed-base multiples
    of G1 — cheap, no 20s pub-table build), shaped like DP ciphertexts."""
    from drynx_tpu.crypto import elgamal as eg

    rng = np.random.default_rng(seed)
    scalars = rng.integers(1, 2 ** 31, size=(k * v * 2,))
    limbs = np.stack([eg.secret_to_limbs(int(s)) for s in scalars])
    pts = np.asarray(eg.fixed_base_mul(eg.BASE_TABLE.table, limbs))
    return pts.reshape(k, v, 2, 3, 16).astype(np.uint32)


def test_fold_cts_mod_p_associativity_byte_identical():
    """Folding the same stack under three different groupings — tree
    halving, left-to-right serial, reversed serial — must land on
    byte-identical canonical tensors. This is the contract the tree/star
    transcript-identity gate rests on: grouping changes Jacobian Z slack,
    canon_points erases it."""
    from drynx_tpu.crypto import batching as B

    stack = _random_ct_stack(k=5, v=3, seed=7)
    tree = np.asarray(topo.fold_cts(stack))

    def serial(parts):
        acc = parts[0]
        for p in parts[1:]:
            acc = B.ct_add(acc, p)
        return np.asarray(topo.canon_points(acc))

    fwd = serial(list(stack))
    rev = serial(list(stack[::-1]))
    assert tree.tobytes() == fwd.tobytes() == rev.tobytes()
    # nested grouping, like a relay folding its subtree before the root
    # folds the relay partials
    sub = np.asarray(topo.fold_cts(stack[2:]))
    nested = np.asarray(topo.fold_cts(np.stack([stack[0], stack[1], sub])))
    assert nested.tobytes() == tree.tobytes()


def test_canon_points_idempotent_and_single_fold():
    stack = _random_ct_stack(k=1, v=2, seed=11)
    one = np.asarray(topo.fold_cts(stack))          # k=1: canon only
    assert one.tobytes() == np.asarray(topo.canon_points(one)).tobytes()
    assert one.shape == stack.shape[1:]


# -- compilecache: the TreeFold program axis --------------------------------

def test_registry_n_fold_adds_treefold_and_zero_is_identity():
    from drynx_tpu import compilecache as cc

    base = cc.Profile(n_cns=2, n_dps=4, n_values=3, u=4, l=2,
                      dlog_limit=100)
    zero = {s.name for s in cc.build_registry(base)}
    one = {s.name for s in cc.build_registry(
        dataclasses.replace(base, n_fold=1))}
    assert one == zero              # a 1-high stack never dispatches adds
    # k=9 (fanout-8 relay + its own contribution) folds at widths
    # {4,2,1}*V; 4*3=12 crosses the bucket boundary above the star
    # registry's n_values=3 aggregation add, so exactly ct_add@16 is new
    tree_specs = cc.build_registry(dataclasses.replace(base, n_fold=9))
    extra = [s for s in tree_specs if s.name not in zero]
    assert [s.name for s in extra] == ["bucketed:ct_add@16"]
    assert all(s.phase == "TreeFold" for s in extra)
    assert zero <= {s.name for s in tree_specs}   # star stays a subset


# -- real-socket surveys: tree vs star --------------------------------------

def _boot(tmp_path, roles, rng):
    """DrynxNode servers named <role><i> with per-role counters; returns
    (nodes, entries, datas-by-name)."""
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.resilience import RetryPolicy

    policy = RetryPolicy(connect_retries=1, backoff_s=0.02,
                         backoff_cap_s=0.05, jitter=0.0,
                         call_timeout_s=rp.CALL_TIMEOUT_S, seed=0)
    nodes, entries, datas, counts = [], [], {}, {}
    for role in roles:
        i = counts.get(role, 0)
        counts[role] = i + 1
        name = f"{role}{i}"
        x, pub = eg.keygen(rng)
        data = None
        if role == "dp":
            data = rng.integers(0, 10, size=(8,)).astype(np.int64)
            datas[name] = data
        n = DrynxNode(name, x, pub, data=data,
                      db_path=str(tmp_path / f"{name}.db"), policy=policy)
        n.start()
        entries.append(RosterEntry(name=name, role=role, host=n.address[0],
                                   port=n.address[1], public=pub))
        nodes.append(n)
    return nodes, entries, datas, policy


def test_tree_vs_star_same_result_fewer_root_bytes(tmp_path, monkeypatch):
    """A 3-level tree (7 DPs, fanout 2) and the star kill-switch must
    agree on the exact sum and the responder list, while the tree run
    lands strictly fewer bytes at the root CN — relays absorb their
    subtrees' payloads and forward one folded partial."""
    from drynx_tpu.crypto import elgamal as eg

    monkeypatch.setenv(topo.ENV_FANOUT, "2")
    rng = np.random.default_rng(41)
    nodes, entries, datas, policy = _boot(
        tmp_path, ["cn"] + ["dp"] * 7, rng)
    try:
        client = RemoteClient(Roster(entries), rng, policy=policy)
        client.broadcast_roster()
        dl = eg.DecryptionTable(limit=1000)
        want = int(sum(d.sum() for d in datas.values()))

        def run(sid):
            set_conn_pool(None)
            res = client.run_survey("sum", query_min=0, query_max=9,
                                    survey_id=sid, dlog=dl)
            return (res, list(client.last_responders),
                    dict(client.last_net.get("rx_by_node") or {}))

        res_t, resp_t, rx_t = run("tv-tree")
        monkeypatch.setenv(topo.ENV_TOPOLOGY, "star")
        res_s, resp_s, rx_s = run("tv-star")
        monkeypatch.delenv(topo.ENV_TOPOLOGY)
    finally:
        for n in nodes:
            n.stop()
    assert res_t == res_s == want
    assert resp_t == resp_s == [f"dp{i}" for i in range(7)]
    # bytes-at-root: the star root hears all 7 DP payloads, the tree
    # root only its 2 forest roots' folded partials
    assert 0 < rx_t["cn0"] < rx_s["cn0"]


@pytest.mark.parametrize("heal_after_s", [None, 0.9])
def test_tree_relay_kill_degrades_only_that_node(tmp_path, monkeypatch,
                                                 heal_after_s):
    """FaultPlan-kill of a MID-TREE relay (dp2 under fanout 2 has the
    children dp6, dp7): only the killed node goes absent — the root
    re-dispatches its children as subtree roots — and the same plan
    yields the same responder set on a second survey across the same
    relay hops (seeded chaos stays deterministic at depth). Killed with a
    heal window, the relay is re-entered once it answers again: the survey
    heals to the exact sum over the FULL roster, collect re-entered from
    its checkpoint and not restarted."""
    from drynx_tpu.crypto import elgamal as eg

    monkeypatch.setenv(topo.ENV_FANOUT, "2")
    rng = np.random.default_rng(42)
    nodes, entries, datas, policy = _boot(
        tmp_path, ["cn"] + ["dp"] * 10, rng)
    try:
        client = RemoteClient(Roster(entries), rng, policy=policy)
        client.broadcast_roster()
        dl = eg.DecryptionTable(limit=1000)
        gone = [] if heal_after_s else ["dp2"]
        want = int(sum(d.sum() for n, d in datas.items() if n not in gone))
        plan = FaultPlan(seed=5)
        set_fault_plan(plan)
        outcomes = []
        for sid in ("kill-a", "kill-b"):
            plan.kill("dp2", heal_after_s=heal_after_s)   # down at dispatch
            res = client.run_survey("sum", query_min=0, query_max=9,
                                    survey_id=sid, dlog=dl,
                                    min_dp_quorum=8)
            outcomes.append((res, list(client.last_responders),
                             list(client.last_absent)))
            if heal_after_s:
                assert client.last_phases.get("collect", 0) >= 2
    finally:
        for n in nodes:
            n.stop()
    for res, resp, absent in outcomes:
        assert res == want
        assert absent == gone                  # dp6/dp7 recovered
        assert resp == [f"dp{i}" for i in range(10) if f"dp{i}" not in gone]
    assert outcomes[0] == outcomes[1]          # deterministic at depth


@pytest.mark.slow
def test_tree_vs_star_vn_transcripts_byte_identical(tmp_path, monkeypatch):
    """Proofs-on acceptance gate: the committed VN audit bitmap (keys +
    verdict codes) must be byte-identical between the tree overlay —
    range proofs riding relay hops as batched blobs, hop aggregation
    proofs parent-verified, VN bitmaps collected up the VN tree — and
    the star kill-switch where every DP fires at the VNs directly."""
    import json as _json

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.proofs import requests as rq

    monkeypatch.setenv(topo.ENV_FANOUT, "2")
    rng = np.random.default_rng(43)
    nodes, entries, datas, policy = _boot(
        tmp_path, ["cn", "dp", "dp", "dp", "vn", "vn", "vn"], rng)
    try:
        client = RemoteClient(Roster(entries), rng, policy=policy)
        client.broadcast_roster()
        dl = eg.DecryptionTable(limit=1000)

        def run(sid):
            set_conn_pool(None)
            result, block = client.run_survey(
                "sum", query_min=0, query_max=9, proofs=True,
                ranges=[(4, 4)], survey_id=sid, dlog=dl, timeout=2400.0)
            norm = {k.replace(sid, "SID"): v
                    for k, v in block["bitmap"].items()}
            return result, _json.dumps(norm, sort_keys=True)

        res_t, tr_t = run("vt-tree")
        monkeypatch.setenv(topo.ENV_TOPOLOGY, "star")
        res_s, tr_s = run("vt-star")
        monkeypatch.delenv(topo.ENV_TOPOLOGY)
    finally:
        for n in nodes:
            n.stop()
    assert res_t == res_s == int(sum(d.sum() for d in datas.values()))
    assert tr_t == tr_s
    bm = json.loads(tr_t)
    assert bm and set(bm.values()) == {rq.BM_TRUE}


# -- satellite: idempotent survey_dp re-entry -------------------------------

def _dp_node(tmp_path):
    from drynx_tpu.crypto import elgamal as eg

    rng = np.random.default_rng(17)
    x, pub = eg.keygen(rng)
    _, cn_pub = eg.keygen(rng)
    node = DrynxNode("dp0", x, pub, data=np.arange(8, dtype=np.int64),
                     db_path=str(tmp_path / "dp0.db"))
    node.roster = Roster([
        RosterEntry(name="cn0", role="cn", host="127.0.0.1", port=0,
                    public=cn_pub),
        RosterEntry(name="dp0", role="dp", host="127.0.0.1", port=0,
                    public=pub)])
    return node


def test_survey_dp_reentry_replays_identical_bytes(tmp_path):
    """Re-entry of survey_dp for the same survey must replay the FIRST
    contribution's exact ciphertext bytes (one encryption ever — a fresh
    one would double-count under tree re-dispatch) and fire the range
    proof at most once."""
    node = _dp_node(tmp_path)
    computed, fired = [], []
    real = node._dp_contribution
    node._dp_contribution = lambda m: (computed.append(1), real(m))[1]
    node._fire_proof_request_async = lambda req: fired.append(req)
    msg = {"type": "survey_dp", "op": "sum", "survey_id": "dup-1",
           "query_min": 0, "query_max": 9, "proofs": False}
    r1 = node._h_survey_dp(dict(msg))
    r2 = node._h_survey_dp(dict(msg))
    assert np.asarray(unpack_array(r1["cts"])).tobytes() \
        == np.asarray(unpack_array(r2["cts"])).tobytes()
    assert len(computed) == 1 and not fired


def test_survey_dp_reentry_fires_proof_once_and_prunes(tmp_path):
    node = _dp_node(tmp_path)
    cts = np.zeros((1, 2, 3, 16), dtype=np.uint32)
    node._dp_contribution = lambda m: (cts, object())   # fake signed req
    fired = []
    node._fire_proof_request_async = lambda req: fired.append(req)
    msg = {"type": "survey_dp", "op": "sum", "survey_id": "dup-2",
           "query_min": 0, "query_max": 9, "proofs": True}
    for _ in range(3):
        node._h_survey_dp(dict(msg))
    assert len(fired) == 1
    # concurrent first entries: one computation, one firing
    node._dp_replies.clear()
    fired.clear()
    ts = [threading.Thread(
        target=lambda i=i: node._h_survey_dp(
            {**msg, "survey_id": "dup-3"})) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(fired) == 1
    # finished foreign surveys are pruned past the cache bound
    for i in range(2 * rp.DP_REPLY_CACHE_MAX):
        node._h_survey_dp({**msg, "proofs": False,
                           "survey_id": f"many-{i}"})
    assert len(node._dp_replies) <= rp.DP_REPLY_CACHE_MAX
