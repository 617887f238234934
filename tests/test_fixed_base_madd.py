"""The fixed-base ladder's window step as plain functions on (16, B) tiles.

`pallas_ops._fixed_base_kernel` adds a table entry to its accumulator with
the mixed (Jacobian + affine) addition of `make_group` and makes its scalar
canonical first. Both are plain jnp functions on limb tiles, so they run
here eagerly, outside any `pallas_call` and outside the interpreter (the
whole ladder through the interpreter is the opt-in tier of
tests/test_pallas_kernels.py), against Python integers and `refimpl`.
And the invariant the kernel rests on: every table `elgamal.FixedBase`
makes is affine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drynx_tpu.crypto import elgamal as eg
from drynx_tpu.crypto import field as F
from drynx_tpu.crypto import pallas_ops as po
from drynx_tpu.crypto import params, refimpl

P, N, R = params.P, params.N, params.R
RNG = np.random.default_rng(29)


def _rand(mod):
    return int.from_bytes(RNG.bytes(40), "little") % (mod - 1) + 1


def _tile(values):
    """ints -> (16, B) uint32 limb tile, one lane a value."""
    return jnp.asarray(F.from_int(values).T)


def _ints(tile):
    return [int(v) for v in F.to_int(np.asarray(tile).T)]


def _jacobian(pt, z):
    """Affine pt (or None) -> Montgomery (X, Y, Z) ints with Z = z."""
    if pt is None:
        return R % P, R % P, 0
    x, y = pt
    return x * z * z * R % P, y * z ** 3 * R % P, z * R % P


def _affine(X, Y, Z):
    """Montgomery Jacobian ints -> affine pt or None."""
    rinv = pow(R, -1, P)
    x, y, z = X * rinv % P, Y * rinv % P, Z * rinv % P
    if z == 0:
        return None
    zi = pow(z, -1, P)
    return x * zi * zi % P, y * zi ** 3 % P


A, B_, C_ = (refimpl.g1_mul(refimpl.G1, _rand(N)) for _ in range(3))
NEG_A = (A[0], P - A[1])
# name -> (accumulator affine, its Jacobian Z, table entry affine)
CASES = {
    "jacobian_plus_affine": (A, _rand(P), B_),
    "jacobian_plus_affine_2": (C_, _rand(P), A),
    "accumulator_at_infinity": (None, 0, B_),
    "entry_at_infinity": (A, _rand(P), None),
    "both_at_infinity": (None, 0, None),
    "opposite_points": (A, _rand(P), NEG_A),
}


@pytest.fixture(scope="module")
def madd_lanes():
    """One eager call of the mixed addition, one lane a case."""
    accs = [_jacobian(a, z) for a, z, _ in CASES.values()]
    ents = [_jacobian(q, 1) for _, _, q in CASES.values()]
    p = tuple(_tile([a[c] for a in accs]) for c in range(3))
    x2, y2, z2 = (_tile([e[c] for e in ents]) for c in range(3))
    m = jnp.asarray(po._M_FP[:, None])
    with jax.enable_x64(False):
        _, _, pmadd = po.make_group(m, po._NPRIME_FP)
        out = pmadd(p, x2, y2, po.fis_zero(z2))
    return p, tuple(_ints(t) for t in out)


@pytest.mark.parametrize("case", list(CASES))
def test_mixed_addition_matches_oracle(madd_lanes, case):
    p, out = madd_lanes
    lane = list(CASES).index(case)
    acc, _, entry = CASES[case]
    got = tuple(c[lane] for c in out)
    assert all(v < P for v in got)
    assert _affine(*got) == refimpl.g1_add(acc, entry)
    if acc is None and entry is not None:
        assert got[2] == R % P          # the entry itself, Z the one
    if entry is None:                   # the accumulator, untouched
        assert got == tuple(_ints(c)[lane] for c in p)


@pytest.mark.parametrize("k", [0, 1, N - 1, N, N + 1, 2 ** 256 - 1],
                         ids=["0", "1", "n-1", "n", "n+1", "2^256-1"])
def test_scalar_made_canonical(k):
    with jax.enable_x64(False):
        got = po.canonical_scalar(_tile([k, 5]),
                                  jnp.asarray(po._N_ORDER[:, None]))
    assert _ints(got) == [k % N, 5]


def _pub():
    return eg.pub_table(refimpl.g1_mul(refimpl.G1, _rand(N)))


@pytest.mark.parametrize("make,finite", [
    (lambda: eg.BASE_TABLE, True), (_pub, True),
    (lambda: eg.FixedBase(None), False)], ids=["base", "pub", "infinity"])
def test_tables_are_affine(make, finite):
    """Z is the Montgomery one, or zero: zero exactly at digit 0 (and in
    every entry of the table of the point at infinity)."""
    z = np.asarray(make().table)[:, :, 2, :]          # (64, 16, 16)
    assert z.shape == (eg.NUM_WINDOWS, eg.WINDOW_SIZE, params.NUM_LIMBS)
    assert not z[:, 0].any()
    if finite:
        assert (z[:, 1:] == po._ONE_MONT).all()
    else:
        assert not z.any()
