"""The obfuscation phase (parallel/obfuscation.py, LocalCluster's
ObfuscationPhase) at a small size: a max over 16 buckets, 3 computing
nodes, 4 data providers.

The guarantee these hold the program to: one pass a computing node, each by
V fresh scalars of its own on the previous node's output. No test here
multiplies two nodes' scalars together to get an "equivalent" answer: what
a pass must give is reckoned pass by pass (crypto/refimpl.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drynx_tpu.crypto import batching as B
from drynx_tpu.crypto import curve as C
from drynx_tpu.crypto import elgamal as eg
from drynx_tpu.crypto import params, refimpl
from drynx_tpu.parallel import dro
from drynx_tpu.parallel import keyswitch as kswitch
from drynx_tpu.parallel import obfuscation as obf
from drynx_tpu.service import node as node_mod
from drynx_tpu.service import service as svc
from drynx_tpu.service.node import DrynxNode, pack_array, unpack_array
from drynx_tpu.utils import exec_store as es
from drynx_tpu.utils.timers import PROCESS

V, N_CNS, N_DPS = 16, 3, 4


@pytest.fixture(scope="module")
def cluster():
    cl = svc.LocalCluster(n_cns=N_CNS, n_dps=N_DPS, n_vns=0, seed=41,
                          dlog_limit=64)
    for dp, value in zip(cl.dps.values(), (3, 11, 7, 11)):
        dp.data = np.asarray([value], dtype=np.int64)
    return cl


def _query(cluster, obfuscation):
    return cluster.generate_survey_query("max", query_min=0, query_max=V - 1,
                                         obfuscation=obfuscation)


def _keeping(calls):
    """`obf.node_pass` with every call's (input, (output, scalars), prove)
    appended to `calls`."""
    real = obf.node_pass

    def spy(key, got, tm=None, prove=None):
        out = real(key, got, tm=tm, prove=prove)
        calls.append((got, out, prove))
        return out
    return spy


def _affine_limbs(points):
    """(x, y, is infinity) of device points, x and y Montgomery limbs."""
    mx, my, inf = C.normalize(jnp.asarray(points))
    return np.asarray(mx), np.asarray(my), np.asarray(inf)


# --- the stored pass -----------------------------------------------------------

@pytest.fixture(scope="module")
def lanes():
    """16 ciphertexts with the identity among them: lane 0 both components,
    lane 1 the second alone (a zero count under r = 0 and a plain one)."""
    rng = np.random.default_rng(43)
    ks = [[int(k) for k in rng.integers(1, 2 ** 62, size=2)]
          for _ in range(V)]
    ks[0] = [0, 0]
    ks[1][1] = 0
    pts = [[refimpl.g1_mul(refimpl.G1, k) for k in pair] for pair in ks]
    cts = jnp.asarray(np.stack([C.from_ref_batch(pair) for pair in pts]))
    s = eg.random_scalars(jax.random.PRNGKey(47), (V,))
    return pts, cts, s


def test_the_pass_is_ct_scalar_mul_limb_for_limb(lanes):
    _, cts, s = lanes
    got = np.asarray(obf._obf_scalar_mul(cts, s))
    assert got.shape == (V, 2, 3, 16) and got.dtype == np.uint32
    assert np.array_equal(got, np.asarray(B.ct_scalar_mul(cts, s)))
    assert np.array_equal(got, np.asarray(eg.ct_scalar_mul(cts, s)))


def test_the_pass_is_the_reference_multiplication_limb_for_limb(lanes):
    pts, cts, s = lanes
    out = obf._obf_scalar_mul(cts, s)
    x, y, inf = _affine_limbs(out)
    scalars = [params.from_limbs(row) for row in np.asarray(s)]
    for i in range(V):
        for c in range(2):
            want = refimpl.g1_mul(pts[i][c], scalars[i])
            assert bool(inf[i, c]) == (want is None), (i, c)
            if want is not None:
                limbs = C.from_ref(want)
                assert np.array_equal(x[i, c], limbs[0]), (i, c)
                assert np.array_equal(y[i, c], limbs[1]), (i, c)
    # the identity stays the identity, and nothing else becomes it
    assert inf[0].all() and inf[1].tolist() == [False, True]
    assert not inf[2:].any()


def test_the_program_is_stored_beside_the_seven():
    assert obf.PROGRAMS == ("_obf_scalar_mul",)
    assert svc.LocalCluster.FUSED == (
        "_fused_enc", "_fused_agg") + kswitch.PROGRAMS + ("_fused_dec",) \
        + dro.PROGRAMS + obf.PROGRAMS
    prog = obf._obf_scalar_mul
    assert isinstance(prog, es.StoredProgram) and es.active() is None
    assert prog.program == prog.__name__ == "_obf_scalar_mul"
    assert prog.reads is es.trace_reads


def test_the_programs_key_holds_the_width_alone():
    def args(v):
        return (jnp.zeros((v, 2, 3, 16), jnp.uint32),
                jnp.zeros((v, 16), jnp.uint32))

    prog = obf._obf_scalar_mul
    assert prog.key(args(12288)) == prog.key(args(12288))
    assert prog.key(args(12288)) != prog.key(args(16384))
    # values are no part of it: the width is all the arguments say
    ones = tuple(jnp.ones_like(a) for a in args(12288))
    assert prog.key(ones) == prog.key(args(12288))
    # no padding to a power of two: the program sees the list as it is
    seen = jax.eval_shape(prog.jit, *args(12288))
    assert seen.shape == (12288, 2, 3, 16)


# --- a node's pass, and who calls it -------------------------------------------

def test_a_pass_draws_fresh_scalars_from_its_key(lanes):
    _, cts, _ = lanes
    before = PROCESS.counter("obf_scalar_muls")
    out_a, s_a = obf.node_pass(jax.random.PRNGKey(1), cts)
    out_b, s_b = obf.node_pass(jax.random.PRNGKey(2), cts)
    again, s_again = obf.node_pass(jax.random.PRNGKey(1), cts)
    assert PROCESS.counter("obf_scalar_muls") - before == 3 * 2 * V
    s_a, s_b = np.asarray(s_a), np.asarray(s_b)
    assert s_a.shape == (V, 16)
    # one scalar a ciphertext, all different, another key's all others
    assert len({row.tobytes() for row in s_a}) == V
    assert not {row.tobytes() for row in s_a} \
        & {row.tobytes() for row in s_b}
    assert np.array_equal(s_a, np.asarray(s_again))
    assert np.array_equal(np.asarray(out_a), np.asarray(again))
    assert np.array_equal(np.asarray(out_a),
                          np.asarray(obf._obf_scalar_mul(cts, s_a)))
    live = ~np.asarray(C.is_infinity(cts))
    changed = (np.asarray(out_a) != np.asarray(cts)).any(axis=(2, 3))
    assert changed[live].all()


def test_proofs_on_the_pass_proves_its_own_step(lanes):
    """`prove` takes the stored program's place and gets the blinding's own
    key, the node's input and the node's scalars."""
    _, cts, _ = lanes
    seen = []

    def prove(k_w, got, s):
        seen.append((k_w, got, s))
        return eg.ct_scalar_mul(got, s)

    before = PROCESS.counter("obf_scalar_muls")
    out, s = obf.node_pass(jax.random.PRNGKey(5), cts, prove=prove)
    plain, s_plain = obf.node_pass(jax.random.PRNGKey(5), cts)
    assert PROCESS.counter("obf_scalar_muls") - before == 2 * 2 * V
    ((k_w, got, s_seen),) = seen
    assert got is cts and s_seen is s
    assert np.array_equal(np.asarray(s), np.asarray(s_plain))
    assert np.array_equal(np.asarray(out), np.asarray(plain))
    k_s, want_w = jax.random.split(jax.random.PRNGKey(5))
    assert np.array_equal(np.asarray(k_w), np.asarray(want_w))
    assert not np.array_equal(np.asarray(k_w), np.asarray(k_s))


def test_a_remote_node_makes_the_same_pass(tmp_path, lanes, monkeypatch):
    _, cts, _ = lanes
    calls = []
    monkeypatch.setattr(obf, "node_pass", _keeping(calls))
    assert node_mod.obf is obf and svc.obf is obf
    x, pub = eg.keygen(np.random.default_rng(53))
    node = DrynxNode("cn0", x, pub, db_path=str(tmp_path / "cn0.db"))
    r = node._h_obf_contrib({"type": "obf_contrib", "survey_id": "s",
                             "proofs": False,
                             "cts": pack_array(np.asarray(cts))})
    ((got, (out, s), prove),) = calls
    assert prove is None and np.array_equal(np.asarray(got),
                                            np.asarray(cts))
    assert np.array_equal(unpack_array(r["cts"]), np.asarray(out))
    assert np.array_equal(np.asarray(out),
                          np.asarray(obf._obf_scalar_mul(cts, s)))
    # a second call draws other scalars: the node's own, from no survey key
    node._h_obf_contrib({"type": "obf_contrib", "survey_id": "s2",
                         "proofs": False,
                         "cts": pack_array(np.asarray(cts))})
    assert not np.array_equal(np.asarray(calls[0][1][1]),
                              np.asarray(calls[1][1][1]))


# --- a survey ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def surveys(cluster):
    """A plain max and an obfuscated one over the same data, the
    obfuscated one's passes recorded as `node_pass` saw and made them."""
    plain = cluster.run_survey(_query(cluster, False), seed=7)
    calls, real = [], obf.node_pass
    counted = PROCESS.counter("obf_scalar_muls")
    obf.node_pass = _keeping(calls)
    try:
        hidden = cluster.run_survey(_query(cluster, True), seed=7)
    finally:
        obf.node_pass = real
    return plain, hidden, calls, PROCESS.counter("obf_scalar_muls") - counted


def test_the_zero_pattern_and_the_answer_survive(surveys):
    plain, hidden, _, _ = surveys
    counts = np.asarray([sum(m > g for m in (3, 11, 7, 11))
                         for g in range(V)])
    assert plain.result == hidden.result == 11
    assert np.array_equal(plain.decrypted.values, counts)
    assert plain.decrypted.found.all()
    assert np.array_equal(hidden.decrypted.is_zero, counts == 0)
    assert np.array_equal(hidden.decrypted.is_zero, plain.decrypted.is_zero)
    # a zero bucket decrypts to zero; no count behind a non-zero bucket
    # comes back: a count times three 254-bit scalars is in no table
    zero = hidden.decrypted.found & (hidden.decrypted.values == 0)
    assert np.array_equal(zero, counts == 0)
    assert not hidden.decrypted.found[counts != 0].any()


def test_three_passes_each_on_the_one_befores_output(cluster, surveys):
    _, _, calls, _ = surveys
    assert len(calls) == N_CNS == len(cluster.cns)
    assert all(prove is None for _, _, prove in calls)
    scalars = [np.asarray(s) for _, (_, s), _ in calls]
    for a in range(N_CNS):
        assert scalars[a].shape == (V, 16)
        for b in range(a + 1, N_CNS):
            assert not (scalars[a] == scalars[b]).all(axis=1).any()
    for before, after in zip(calls, calls[1:]):
        assert after[0] is before[1][0]
    # pass by pass against the reference: M_i = s_i * M_(i-1), on the
    # points the collective secret decrypts the list to
    x = jnp.asarray(eg.secret_to_limbs(
        sum(c.secret for c in cluster.cns) % params.N))
    points = C.to_ref(eg.decrypt_point(calls[0][0], x))
    counts = [sum(m > g for m in (3, 11, 7, 11)) for g in range(V)]
    assert points == [refimpl.g1_mul(refimpl.G1, c) for c in counts]
    for got_in, (out, s), _ in calls:
        points = [refimpl.g1_mul(p, params.from_limbs(row))
                  for p, row in zip(points, np.asarray(s))]
        assert C.to_ref(eg.decrypt_point(out, x)) == points
        assert not (np.asarray(out) == np.asarray(got_in)).all(
            axis=(1, 2, 3)).any()


def test_the_counter_and_the_steps(surveys):
    plain, hidden, _, counted = surveys
    assert counted == 2 * V * N_CNS
    steps = [name for name, _, _ in hidden.timers.spans("ObfuscationPhase")]
    assert steps[0] == "ObfuscationPhase"
    assert steps[1:] == ["ObfuscationPhase/randomness",
                         "ObfuscationPhase/mul"] * N_CNS
    (phase,) = [(a, b) for name, a, b in hidden.timers.spans()
                if name == "ObfuscationPhase"]
    inside = hidden.timers.spans("ObfuscationPhase/")
    assert all(phase[0] <= a <= b <= phase[1] for _, a, b in inside)
    assert not plain.timers.spans("ObfuscationPhase")


def test_a_survey_without_obfuscation_never_reaches_the_program(
        cluster, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("a survey without obfuscation came here")

    monkeypatch.setattr(obf, "_obf_scalar_mul", refuse)
    monkeypatch.setattr(obf, "node_pass", refuse)
    before = PROCESS.counter("obf_scalar_muls")
    res = cluster.run_survey(_query(cluster, False), seed=9)
    assert res.result == 11
    assert PROCESS.counter("obf_scalar_muls") == before
    with pytest.raises(AssertionError):
        cluster.run_survey(_query(cluster, True), seed=9)
