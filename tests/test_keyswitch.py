"""The key switch (parallel/keyswitch.py, LocalCluster.key_switch) at a small
size: a max over 16 buckets, 4 data providers, rosters of 1, 3 and 7
computing nodes.

The guarantee these hold the program to: one contribution a computing node,
made with that node's own secret and V fresh scalars of its own. No test
here adds two nodes' secrets into one scalar to get an "equivalent" answer:
what a pass must give is reckoned node by node (crypto/refimpl.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drynx_tpu.crypto import curve as C
from drynx_tpu.crypto import elgamal as eg
from drynx_tpu.crypto import params, refimpl
from drynx_tpu.parallel import dro
from drynx_tpu.parallel import keyswitch as kswitch
from drynx_tpu.parallel import obfuscation as obf
from drynx_tpu.proofs import keyswitch as ks_proof
from drynx_tpu.service import node as node_mod
from drynx_tpu.service import service as svc
from drynx_tpu.service.node import DrynxNode, pack_array, unpack_array
from drynx_tpu.utils import exec_store as es
from drynx_tpu.utils.timers import PROCESS

V, N_DPS = 16, 4
VALUES = (3, 11, 7, 11)
COUNTS = np.asarray([sum(m > g for m in VALUES) for g in range(V)])
ROSTERS = (1, 3, 7)


def _cluster(n_cns):
    cl = svc.LocalCluster(n_cns=n_cns, n_dps=N_DPS, n_vns=0, seed=41,
                          dlog_limit=64)
    for dp, value in zip(cl.dps.values(), VALUES):
        dp.data = np.asarray([value], dtype=np.int64)
    return cl


def _keeping(calls):
    """`kswitch.node_pass` with every call's (key, acc handed in, what it
    gave back) appended to `calls`."""
    real = kswitch.node_pass

    def spy(key, K0, x, q_tbl, acc=None, tm=None):
        out = real(key, K0, x, q_tbl, acc, tm=tm)
        calls.append((key, K0, x, acc, out))
        return out
    return spy


@pytest.fixture(scope="module")
def surveys():
    """A max on each roster over the same data, every node's pass recorded
    as `node_pass` saw and made it: {n_cns: (cluster, result, calls,
    counted)}."""
    out = {}
    for n_cns in ROSTERS:
        cluster = _cluster(n_cns)
        calls, real = [], kswitch.node_pass
        counted = PROCESS.counter("ks_contributions")
        kswitch.node_pass = _keeping(calls)
        try:
            result = cluster.run_survey(cluster.generate_survey_query(
                "max", query_min=0, query_max=V - 1), seed=7)
        finally:
            kswitch.node_pass = real
        out[n_cns] = (cluster, result, calls,
                      PROCESS.counter("ks_contributions") - counted)
    return out


# --- a node's pass -----------------------------------------------------------------

@pytest.fixture(scope="module")
def lanes():
    """16 points with the identity among them, a secret and a querier."""
    rng = np.random.default_rng(43)
    ks = [int(k) for k in rng.integers(1, 2 ** 62, size=V)]
    ks[0] = 0
    pts = [refimpl.g1_mul(refimpl.G1, k) for k in ks]
    x, _ = eg.keygen(rng)
    _, q_pub = eg.keygen(rng)
    return (pts, jnp.asarray(C.from_ref_batch(pts)), x,
            jnp.asarray(eg.secret_to_limbs(x)), q_pub, eg.pub_table(q_pub))


def _sub(p, q):
    return refimpl.g1_add(p, refimpl.g1_neg(q))


def test_a_pass_is_the_reference_contribution_point_by_point(lanes):
    pts, K0, x, x_limbs, q_pub, q_tbl = lanes
    before = PROCESS.counter("ks_contributions")
    (k_sum, c_sum), (u, w, r) = kswitch.node_pass(
        jax.random.PRNGKey(1), K0, x_limbs, q_tbl.table)
    assert PROCESS.counter("ks_contributions") - before == V
    assert u.shape == w.shape == (V, 3, 16) and r.shape == (V, 16)
    rs = [params.from_limbs(row) for row in np.asarray(r)]
    assert len(set(rs)) == V and all(0 < v < params.N for v in rs)
    assert C.to_ref(u) == [refimpl.g1_mul(refimpl.G1, v) for v in rs]
    assert C.to_ref(w) == [
        _sub(refimpl.g1_mul(q_pub, v), refimpl.g1_mul(p, x))
        for v, p in zip(rs, pts)]
    # started at the identity, the sums ARE the contribution, byte for byte
    assert np.array_equal(np.asarray(k_sum), np.asarray(u))
    assert np.array_equal(np.asarray(c_sum), np.asarray(w))
    # a second node's pass adds its own to them
    x2, _ = eg.keygen(np.random.default_rng(59))
    (k2, c2), (u2, w2, r2) = kswitch.node_pass(
        jax.random.PRNGKey(2), K0, jnp.asarray(eg.secret_to_limbs(x2)),
        q_tbl.table, (k_sum, c_sum))
    assert not {row.tobytes() for row in np.asarray(r)} \
        & {row.tobytes() for row in np.asarray(r2)}
    assert C.to_ref(k2) == [refimpl.g1_add(a, b) for a, b
                            in zip(C.to_ref(u), C.to_ref(u2))]
    assert C.to_ref(c2) == [refimpl.g1_add(a, b) for a, b
                            in zip(C.to_ref(w), C.to_ref(w2))]
    rs2 = [params.from_limbs(row) for row in np.asarray(r2)]
    assert C.to_ref(w2) == [
        _sub(refimpl.g1_mul(q_pub, v), refimpl.g1_mul(p, x2))
        for v, p in zip(rs2, pts)]
    # the same key draws the same scalars and makes the same bytes
    _, (u_again, w_again, r_again) = kswitch.node_pass(
        jax.random.PRNGKey(1), K0, x_limbs, q_tbl.table)
    assert np.array_equal(np.asarray(r), np.asarray(r_again))
    assert np.array_equal(np.asarray(u), np.asarray(u_again))
    assert np.array_equal(np.asarray(w), np.asarray(w_again))


def test_the_finish_adds_the_sums_and_takes_the_shift_off(lanes):
    pts, K0, *_ = lanes
    agg = jnp.stack([K0, K0[::-1]], axis=1)
    acc = (K0[::-1], K0)
    plain = kswitch.finish(agg, acc)
    assert plain.shape == (V, 2, 3, 16)
    assert C.to_ref(plain[:, 0]) == pts[::-1]
    both = [refimpl.g1_add(a, b) for a, b in zip(pts[::-1], pts)]
    assert C.to_ref(plain[:, 1]) == both
    shifted = kswitch.finish(agg, acc, offset_total=5)
    five = refimpl.g1_mul(refimpl.G1, 5)
    assert C.to_ref(shifted[:, 1]) == [_sub(p, five) for p in both]
    assert np.array_equal(np.asarray(shifted[:, 0]), np.asarray(plain[:, 0]))
    with pytest.raises(AssertionError):
        kswitch.finish(agg, acc, offset_total=2 ** 62)


def test_the_programs_are_stored_in_fused_kss_place():
    assert kswitch.PROGRAMS == ("_ks_pass", "_ks_finish")
    assert svc.LocalCluster.FUSED == (
        "_fused_enc", "_fused_agg") + kswitch.PROGRAMS + ("_fused_dec",) \
        + dro.PROGRAMS + obf.PROGRAMS
    assert not hasattr(svc, "_fused_ks")
    for name in kswitch.PROGRAMS:
        prog = getattr(kswitch, name)
        assert isinstance(prog, es.StoredProgram) and es.active() is None
        assert prog.program == prog.__name__ == name
        assert prog.reads is es.trace_reads


def test_the_programs_keys_hold_the_width_alone():
    def args(v):
        pts = jnp.zeros((v, 3, 16), jnp.uint32)
        return (jnp.zeros((64, 16, 3, 16), jnp.uint32), pts,
                jnp.zeros((16,), jnp.uint32), jnp.zeros((v, 16), jnp.uint32),
                pts, pts)

    prog = kswitch._ks_pass
    assert prog.key(args(12288)) == prog.key(args(12288))
    assert prog.key(args(12288)) != prog.key(args(16384))
    ones = tuple(jnp.ones_like(a) for a in args(12288))
    assert prog.key(ones) == prog.key(args(12288))
    # nothing the program takes or gives is as wide as a roster, and the
    # list is not padded to a power of two
    seen = jax.eval_shape(prog.jit, *args(12288))
    assert [s.shape for s in seen] == [(12288, 3, 16)] * 4
    done = jax.eval_shape(
        kswitch._ks_finish.jit, jnp.zeros((12288, 2, 3, 16), jnp.uint32),
        args(12288)[1], args(12288)[1], jnp.asarray(0, dtype=jnp.int64))
    assert done.shape == (12288, 2, 3, 16)


# --- a survey, on three rosters -------------------------------------------------

@pytest.mark.parametrize("n_cns", ROSTERS)
def test_a_survey_decrypts_to_the_clear_answer_on_every_roster(surveys,
                                                               n_cns):
    cluster, result, calls, counted = surveys[n_cns]
    assert len(cluster.cns) == n_cns
    assert result.result == max(VALUES)
    assert result.decrypted.found.all()
    assert np.array_equal(result.decrypted.values, COUNTS)
    # one pass a node, each with its own secret and its own scalars, each
    # handed the pass before's sums
    assert len(calls) == n_cns and counted == n_cns * V
    secrets = [params.from_limbs(np.asarray(x)) for _, _, x, _, _ in calls]
    assert secrets == [c.secret for c in cluster.cns]
    assert calls[0][3] is None
    for before, after in zip(calls, calls[1:]):
        assert after[3] is before[4][0]
    rows = [row.tobytes() for *_, (_, (_, _, r)) in calls
            for row in np.asarray(r)]
    assert len(set(rows)) == n_cns * V
    steps = [name for name, _, _ in result.timers.spans("KeySwitchingPhase")]
    assert steps == ["KeySwitchingPhase", "KeySwitchingPhase/secrets"] \
        + ["KeySwitchingPhase/randomness", "KeySwitchingPhase/pass"] * n_cns \
        + ["KeySwitchingPhase/finish"]


def test_one_program_serves_every_roster(surveys):
    """Three rosters ran; the pass was traced for one shape, V."""
    assert set(surveys) == set(ROSTERS)
    assert kswitch._ks_pass.jit._cache_size() <= 2      # V lanes, `lanes`
    (sig,) = {tuple(a.shape for a in (K0, x, out[0][0], out[1][0]))
              for _, K0, x, _, out in
              (c for _, _, calls, _ in surveys.values() for c in calls)}
    assert sig == ((V, 3, 16), (16,), (V, 3, 16), (V, 3, 16))


def test_a_pass_left_out_resolves_no_bucket(surveys):
    """The switched ciphertexts without the last node's contribution still
    carry its x K: the querier's table resolves nothing."""
    cluster, result, calls, _ = surveys[3]
    _, K0, _, last_acc_in, (acc, _) = calls[-1]
    _, _, f_dec = cluster._fused()
    dl = cluster.dlog
    xq = jnp.asarray(eg.secret_to_limbs(cluster.client.secret))
    # the aggregate's C component, from what the survey decrypted to: the
    # whole key switch gives (k_sum, C + c_sum), so C = switched - c_sum
    counts_b = jnp.asarray(C.from_ref_batch(
        [refimpl.g1_mul(refimpl.G1, int(c)) for c in COUNTS]))
    x_all = sum(c.secret for c in cluster.cns) % params.N
    c_comp = C.add(counts_b, C.scalar_mul(
        K0, jnp.asarray(eg.secret_to_limbs(x_all))))
    agg = jnp.stack([K0, c_comp], axis=1)
    for sums, resolves in ((acc, True), (last_acc_in, False)):
        vals, found, _ = f_dec(kswitch.finish(agg, sums), xq, dl.keys,
                               dl.xs, dl.ysign, dl.vals)
        assert bool(np.asarray(found).all()) is resolves
        assert bool(np.asarray(found).any()) is resolves
        if resolves:
            assert np.array_equal(np.asarray(vals), COUNTS)


def test_proofs_on_the_stacked_contributions_still_prove(surveys):
    """What execute_survey hands the proof with proofs on: every node's own
    (U_i, W_i, r_i), stacked in roster order (four buckets of them here:
    the proof is a ciphertext's and a node's, and its kernels are dear)."""
    cluster, _, calls, _ = surveys[3]
    K0 = calls[0][1]
    agg = jnp.stack([K0, K0], axis=1)
    switched, kept = cluster.key_switch(jax.random.PRNGKey(5), agg,
                                        keep=True)
    assert len(kept) == 3 and switched.shape == (V, 2, 3, 16)
    _, none_kept = cluster.key_switch(jax.random.PRNGKey(5), agg)
    assert none_kept == []
    u_pts, w_pts, ks_rs = (jnp.stack(c)[:, :4] for c in zip(*kept))
    srv_x = jnp.asarray(np.stack([eg.secret_to_limbs(c.secret)
                                  for c in cluster.cns]))
    proof = ks_proof.create_keyswitch_proofs(
        jax.random.PRNGKey(6), K0[:4], srv_x, ks_rs, cluster.client_pt,
        cluster.client_tbl.table, u_pts, w_pts)
    assert bool(np.all(ks_proof.verify_keyswitch_proofs(
        proof, cluster.client_tbl.table)))
    # a contribution made with another node's secret does not prove
    bad = ks_proof.create_keyswitch_proofs(
        jax.random.PRNGKey(6), K0[:4], srv_x, ks_rs, cluster.client_pt,
        cluster.client_tbl.table, u_pts, w_pts.at[0].set(w_pts[1]))
    assert not bool(np.all(ks_proof.verify_keyswitch_proofs(
        bad, cluster.client_tbl.table)))


def test_a_remote_node_makes_the_same_pass(tmp_path, lanes, monkeypatch):
    _, K0, x, x_limbs, q_pub, q_tbl = lanes
    calls = []
    monkeypatch.setattr(kswitch, "node_pass", _keeping(calls))
    assert node_mod.kswitch is kswitch and svc.kswitch is kswitch
    _, pub = eg.keygen(np.random.default_rng(53))
    node = DrynxNode("cn0", x, pub, db_path=str(tmp_path / "cn0.db"))
    frame = {"type": "ks_contrib", "survey_id": "s", "proofs": False,
             "k_component": pack_array(np.asarray(K0)),
             "client_pub": list(q_pub)}
    reply = node._h_ks_contrib(frame)
    ((key, got, x_seen, acc, (_, (u, w, _))),) = calls
    assert acc is None and np.array_equal(np.asarray(got), np.asarray(K0))
    assert np.array_equal(np.asarray(x_seen), np.asarray(x_limbs))
    assert np.array_equal(unpack_array(reply["u"]), np.asarray(u))
    assert np.array_equal(unpack_array(reply["w"]), np.asarray(w))
    # the bytes LocalCluster.key_switch's pass makes from the same key
    monkeypatch.undo()
    (k_sum, c_sum), _ = kswitch.node_pass(key, K0, x_limbs, q_tbl.table)
    assert np.array_equal(unpack_array(reply["u"]), np.asarray(k_sum))
    assert np.array_equal(unpack_array(reply["w"]), np.asarray(c_sum))
    # a second call draws other scalars: the node's own, from no survey key
    again = node._h_ks_contrib(dict(frame, survey_id="s2"))
    assert not np.array_equal(unpack_array(again["u"]),
                              unpack_array(reply["u"]))
