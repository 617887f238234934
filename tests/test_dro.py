"""The noise phase (parallel/dro.py, LocalCluster's DROPhase) at a small
size: a list of 64 in slabs of 16, 3 computing nodes, 4 data providers.

The plain reference the program is held to is the benchmark's own
(benchmarks/reference/sum_diffp.py: numpy, imports nothing of the program),
loaded by its path.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drynx_tpu.crypto import curve as C
from drynx_tpu.crypto import elgamal as eg
from drynx_tpu.crypto import pallas_ops as po
from drynx_tpu.crypto import params, refimpl
from drynx_tpu.parallel import dro
from drynx_tpu.service import service as svc
from drynx_tpu.service.query import DiffPParams
from drynx_tpu.utils import exec_store as es
from drynx_tpu.utils.timers import PROCESS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE, SLAB, N_CNS, N_DPS = 64, 16, 3, 4
LAPLACE = dict(lap_mean=0.0, lap_scale=2.0, quanta=1.0, scale=1.0, limit=8.0)
# the same, as `dro.generate_noise_values` and the reference name them
LIST_ARGS = (LAPLACE["lap_mean"], LAPLACE["lap_scale"], LAPLACE["quanta"],
             LAPLACE["scale"], LAPLACE["limit"])


def _reference():
    spec = importlib.util.spec_from_file_location(
        "_reference_sum_diffp",
        os.path.join(ROOT, "benchmarks", "reference", "sum_diffp.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _noise(size=SIZE):
    return REF.noise_list(size, *LIST_ARGS)


# --- the list --------------------------------------------------------------

@pytest.mark.parametrize("size,scale,quanta,limit", [
    (64, 1.0, 1.0, 8.0), (64, 1.0, 1.0, 0.0), (16, 1.0, 1.0, 8.0),
    (1000, 1.0, 1.0, 400.0), (1000, 10.0, 0.5, 12.0), (333, 3.0, 2.0, 7.0),
    (4096, 1.0, 1.0, 400.0), (5, 1.0, 100.0, 0.0), (1, 1.0, 1.0, 0.0)])
def test_the_noise_list_is_the_references_own(size, scale, quanta, limit):
    got = dro.generate_noise_values(size, 0.0, 20.0, quanta, scale, limit)
    want = REF.noise_list(size, 0.0, 20.0, quanta, scale, limit)
    assert got.dtype == want.dtype == np.int64
    assert np.array_equal(got, want)


# --- the three passes, outside a survey --------------------------------------

@pytest.fixture(scope="module")
def cluster():
    cl = svc.LocalCluster(n_cns=N_CNS, n_dps=N_DPS, n_vns=0, seed=29,
                          dlog_limit=2000)
    rng = np.random.default_rng(31)
    for dp in cl.dps.values():
        dp.data = rng.integers(0, 16, size=(24,)).astype(np.int64)
    return cl


@pytest.fixture(scope="module")
def passes(cluster):
    """The list encrypted and passed through every node, in slabs of 16
    and in one dispatch, for one key: per pass (slabbed, whole, perm)."""
    noise = dro.generate_noise_values(SIZE, *LIST_ARGS)
    tbl = cluster.coll_tbl
    k_enc, *k_nodes = jax.random.split(jax.random.PRNGKey(5), 1 + N_CNS)
    slabbed = dro.encrypt_noise(k_enc, tbl, noise, chunk=SLAB)
    whole = dro.encrypt_noise(k_enc, tbl, noise, chunk=0)
    out = [(slabbed, whole, None)]
    for k in k_nodes:
        s, perm, _ = dro.node_pass(k, out[-1][0], tbl.table, chunk=SLAB)
        w, perm_w, _ = dro.node_pass(k, out[-1][1], tbl.table, chunk=0)
        assert np.array_equal(np.asarray(perm), np.asarray(perm_w))
        out.append((s, w, np.asarray(perm)))
    return noise, out


def _collective_secret(cluster) -> int:
    return sum(c.secret for c in cluster.cns) % params.N


def _decrypt(cluster, cts) -> np.ndarray:
    vals, found = eg.decrypt_ints(cts, _collective_secret(cluster),
                                  eg.DecryptionTable(limit=16))
    assert bool(np.all(np.asarray(found)))
    return np.asarray(vals)


def test_the_slab_path_equals_one_dispatch_byte_for_byte(passes):
    _, out = passes
    assert len(out) == 1 + N_CNS
    for slabbed, whole, _ in out:
        assert slabbed.shape == (SIZE, 2, 3, 16)
        assert np.array_equal(np.asarray(slabbed), np.asarray(whole))


@pytest.fixture(scope="module")
def decrypted(cluster, passes):
    """The list after the encryption and after every pass, decrypted."""
    return [_decrypt(cluster, slabbed) for slabbed, _, _ in passes[1]]


@pytest.mark.parametrize("node", range(N_CNS + 1))
def test_after_every_pass_the_list_is_the_references_multiset(
        passes, decrypted, node):
    noise, out = passes
    assert np.array_equal(noise, _noise())
    got = decrypted[node]
    assert np.array_equal(np.sort(got), np.sort(_noise()))
    if node == 0:
        assert np.array_equal(got, noise)       # encrypted in order
    else:
        assert np.array_equal(got, decrypted[node - 1][out[node][2]])


@pytest.mark.parametrize("node", range(1, N_CNS + 1))
def test_a_pass_permutes_and_rerandomises_every_ciphertext(passes, node):
    _, out = passes
    perm = out[node][2]
    assert sorted(perm.tolist()) == list(range(SIZE))
    assert not np.array_equal(perm, np.arange(SIZE))
    assert all(not np.array_equal(out[node][2], out[m][2])
               for m in range(1, node))         # a fresh one every pass
    pre_image = np.asarray(out[node - 1][0])[perm]
    after = np.asarray(out[node][0])
    unchanged = [i for i in range(SIZE)
                 if np.array_equal(after[i], pre_image[i])]
    assert unchanged == []
    # both components moved: (K, C) -> (K + rB, C + rP)
    assert all(not np.array_equal(after[i, c], pre_image[i, c])
               for i in range(SIZE) for c in (0, 1))


# --- the re-randomising addition in the Pallas kernel (a TPU's path) ----------

# what a lane's two operands are, for every branch of the complete addition
LANES = ("left_infinity", "right_infinity", "both_infinity", "equal_bytes",
         "equal_points", "opposite", "generic")


def _slab_with_every_case(size, n):
    """(cts, idx, zero_ct, lanes): a slab of n of a list of `size` Jacobian
    ciphertexts (Z != 1) whose first lanes hold LANES in the first
    component and, further on, in the second; the others are generic."""
    assert 2 * len(LANES) <= n <= size
    ks = np.arange(2, 2 + 2 * (size + n))
    aff = jnp.asarray(C.from_ref_batch(
        [refimpl.g1_mul(refimpl.G1, int(k)) for k in ks]))
    jac = np.asarray(C.add(aff, jnp.roll(aff, 1, axis=0)))   # (k + k') G
    mult = ks + np.roll(ks, 1)
    cts, zero = jac[:2 * size].copy(), jac[2 * size:].copy()
    cts, zero = cts.reshape(size, 2, 3, 16), zero.reshape(n, 2, 3, 16)
    idx = np.random.default_rng(7).permutation(size)[:n].astype(np.int32)
    inf = np.asarray(C.infinity())
    lanes = {}
    for c in (0, 1):
        for j, case in enumerate(LANES[:-1]):
            i = c * len(LANES) + j
            lanes[i, c] = case
            if case in ("left_infinity", "both_infinity"):
                cts[idx[i], c] = inf
            if case in ("right_infinity", "both_infinity"):
                zero[i, c] = inf
            if case == "equal_bytes":
                zero[i, c] = cts[idx[i], c]
            if case == "equal_points":      # the affine form of the same point
                k = int(mult[2 * idx[i] + c])
                zero[i, c] = C.from_ref(refimpl.g1_mul(refimpl.G1, k))
            if case == "opposite":
                zero[i, c] = np.asarray(C.neg(jnp.asarray(cts[idx[i], c])))
    return jnp.asarray(cts), jnp.asarray(idx), jnp.asarray(zero), lanes


def _jnp_pass(cts, idx, zero_ct):
    return np.asarray(eg.ct_add(jnp.take(cts, idx, axis=0), zero_ct))


def test_the_kernel_path_flattens_and_restores_the_slab(monkeypatch):
    """(4.4 s.) With the kernel replaced by `C.add` on the flat batch, the
    TPU branch of `_dro_permute_add` gives the jnp path's bytes at a slab
    that is no multiple of 128: the flatten and its inverse are pinned
    without an interpreter compile."""
    size, n = 50, 37
    cts, idx, zero, _ = _slab_with_every_case(size, n)
    seen = []

    def stand_in(p, q):
        seen.append((p.shape, q.shape))
        return C.add(p, q)

    monkeypatch.setattr(po, "available", lambda: True)
    monkeypatch.setattr(po, "point_add_flat", stand_in)
    got = dro._dro_permute_add.jit.__wrapped__(cts, idx, zero)
    assert seen == [((2 * n, 3, 16), (2 * n, 3, 16))]
    assert got.shape == zero.shape and got.dtype == zero.dtype
    assert np.array_equal(np.asarray(got), _jnp_pass(cts, idx, zero))


def test_the_kernels_infinity_is_the_jnp_layers():
    """Opposite operands sum to the infinity the kernel writes: the limbs
    of `curve.infinity`, or the two paths' bytes part there (0.1 s)."""
    tile = jnp.zeros((16, 4), jnp.uint32)
    got = np.asarray(jnp.stack(po._inf_like((tile, tile, tile))))
    assert np.array_equal(np.moveaxis(got, -1, 0),
                          np.asarray(C.infinity((4,))))


@pytest.mark.slow(reason="64 s alone on the 8-core sandbox, test compile "
                         "cache off (PR 31): one interpreter compile of "
                         "the complete addition's 30 field products")
def test_the_add_kernel_is_curve_add_byte_for_byte_in_every_case(
        monkeypatch):
    """The real kernel, through `_dro_permute_add`'s TPU branch, against
    `curve.add`: infinity on either side and on both, equal operands in
    one and in two representations, opposite operands, generic ones."""
    size, n = 20, 2 * len(LANES)
    cts, idx, zero, lanes = _slab_with_every_case(size, n)
    monkeypatch.setattr(po, "INTERPRET", True)
    assert po.available()
    got = np.asarray(dro._dro_permute_add.jit.__wrapped__(cts, idx, zero))
    want = _jnp_pass(cts, idx, zero)
    differing = [(i, c, lanes.get((i, c), "generic"))
                 for i in range(n) for c in (0, 1)
                 if not np.array_equal(got[i, c], want[i, c])]
    assert differing == []
    # and the cases are the cases: which lanes come out as infinity
    at_infinity = {k for k in lanes if not got[k][2].any()}
    assert at_infinity == {k for k, case in lanes.items()
                           if case in ("both_infinity", "opposite")}
    picked = np.asarray(cts)[np.asarray(idx)]
    for (i, c), case in lanes.items():
        if case == "left_infinity":
            assert np.array_equal(got[i, c], np.asarray(zero)[i, c])
        if case == "right_infinity":
            assert np.array_equal(got[i, c], picked[i, c])
        if case in ("equal_bytes", "equal_points"):
            assert np.array_equal(
                got[i, c], np.asarray(C.double(jnp.asarray(picked[i, c]))))


def test_a_zero_encryption_is_todays_bytes_without_the_zero_ladder(cluster):
    """`_dro_zero_enc` computes (rB, rP); `encrypt_with_tables` on zero
    scalars computes (rB, 0*B + rP): the same bytes for the same r."""
    tbl = cluster.coll_tbl.table
    zero_ct, r = dro.precompute_rerandomization(
        jax.random.PRNGKey(9), tbl, SIZE, chunk=SLAB)
    zeros = eg.int_to_scalar(jnp.zeros((SLAB,), dtype=jnp.int64))
    want = eg.encrypt_with_tables(eg.BASE_TABLE.table, tbl, zeros, r[:SLAB])
    assert np.array_equal(np.asarray(zero_ct[:SLAB]), np.asarray(want))
    assert np.array_equal(np.asarray(r), np.asarray(
        eg.random_scalars(jax.random.PRNGKey(9), (SIZE,))))


# --- through run_survey -------------------------------------------------------

CASES = {
    # (list, slab): the list in four slabs
    "list64_slab16": (64, 16),
    # tests/test_service_e2e.py::test_survey_diffp_adds_noise's parameters:
    # the whole list is one slab
    "list16_one_slab": (16, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_diffp_sum_survey_adds_a_member_of_the_list(cluster, monkeypatch,
                                                      case):
    size, slab = CASES[case]
    monkeypatch.setattr(dro, "CHUNK", slab)
    final = []
    real_pick = dro.pick_add

    def pick(agg, cts, tm=None):
        final.append(cts)
        return real_pick(agg, cts, tm=tm)

    monkeypatch.setattr(dro, "pick_add", pick)
    sq = cluster.generate_survey_query(
        "sum", query_min=0, query_max=15, proofs=0,
        diffp=DiffPParams(noise_list_size=size, **LAPLACE))
    before = PROCESS.counter("dro_encryptions")
    res = cluster.run_survey(sq, seed=77)

    clear = int(sum(int(dp.data.sum()) for dp in cluster.dps.values()))
    members = set(_noise(size).tolist())
    drawn = int(res.result) - clear
    assert drawn in members
    assert int(res.decrypted.values[0]) == int(res.result)
    (final_list,) = final
    decrypted = _decrypt(cluster, final_list)
    assert np.array_equal(np.sort(decrypted), np.sort(_noise(size)))
    assert drawn == int(decrypted[0])

    # the phase's steps, the three nodes' under one name each
    steps = [name for name, _, _ in res.timers.spans("DROPhase")]
    assert steps[0] == "DROPhase"
    want = {"noise_values": 1, "noise_enc": 1, "zero_enc": N_CNS,
            "permute_add": N_CNS, "pick_add": 1}
    assert {s: steps.count(f"DROPhase/{s}") for s in want} == want
    assert len(steps) == 1 + sum(want.values())
    (phase,) = [(a, b) for name, a, b in res.timers.spans()
                if name == "DROPhase"]
    assert all(phase[0] <= a <= b <= phase[1]
               for _, a, b in res.timers.spans("DROPhase/"))
    # every noise value encrypted once, and a fresh zero for every
    # ciphertext in every node's pass: nothing pooled, nothing reused
    assert PROCESS.counter("dro_encryptions") - before == size * (1 + N_CNS)


# --- the stored programs ------------------------------------------------------

def test_the_slab_programs_are_stored_beside_the_four():
    assert svc.LocalCluster.FUSED[:5] == (
        "_fused_enc", "_fused_agg", "_ks_pass", "_ks_finish", "_fused_dec")
    assert svc.LocalCluster.FUSED[5:8] == dro.PROGRAMS == (
        "_dro_noise_enc", "_dro_zero_enc", "_dro_permute_add")
    for name in dro.PROGRAMS:
        prog = getattr(dro, name)
        assert isinstance(prog, es.StoredProgram) and es.active() is None
        assert prog.program == prog.__name__ == name
        assert prog.reads is svc._trace_reads is es.trace_reads


def _slab_args(name, size, width):
    fb = jnp.zeros((64, 16, 3, 16), jnp.uint32)
    r = jnp.zeros((width, 16), jnp.uint32)
    ct = jnp.zeros((width, 2, 3, 16), jnp.uint32)
    return {"_dro_noise_enc": (fb, fb, jnp.zeros((width,), jnp.int64), r),
            "_dro_zero_enc": (fb, fb, r),
            "_dro_permute_add": (jnp.zeros((size, 2, 3, 16), jnp.uint32),
                                 jnp.zeros((width,), jnp.int32), ct)}[name]


@pytest.mark.parametrize("name", dro.PROGRAMS)
def test_a_slab_programs_key_changes_with_the_slab_shape(name):
    prog = getattr(dro, name)
    base = prog.key(_slab_args(name, 262144, 4096))
    assert base == prog.key(_slab_args(name, 262144, 4096))
    assert base != prog.key(_slab_args(name, 262144, 2048))
    # the two ladders' programs serve every list size at one width; the
    # gather reads the whole list, so its key holds the list's size too
    other_size = prog.key(_slab_args(name, 524288, 4096))
    assert (base != other_size) == (name == "_dro_permute_add")
    others = [getattr(dro, n).key(_slab_args(n, 262144, 4096))
              for n in dro.PROGRAMS if n != name]
    assert base not in others


def test_slab_widths_are_what_the_phase_dispatches(monkeypatch):
    assert dro.slab_widths(262144) == [4096]
    assert dro.slab_widths(10000) == [10000 - 2 * 4096, 4096]
    assert dro.slab_widths(64, chunk=16) == [16]
    assert dro.slab_widths(64, chunk=0) == dro.slab_widths(64) == [64]
    seen = []
    real = dro._dro_zero_enc
    monkeypatch.setattr(dro, "_dro_zero_enc", lambda b, p, r: (
        seen.append(int(r.shape[0])), real(b, p, r))[1])
    tbl = eg.BASE_TABLE.table
    dro.precompute_rerandomization(jax.random.PRNGKey(1), tbl, 40,
                                   chunk=SLAB)
    assert seen == [16, 16, 8]
    assert sorted(set(seen)) == dro.slab_widths(40, chunk=SLAB)
