"""The two ladders' window steps as plain functions, in both layouts.

`pallas_ops._fixed_base_kernel` adds a table entry to its accumulator with
the mixed (Jacobian + affine) addition of `make_group` and makes its scalar
canonical first; `_scalar_mul_kernel` adds one with `paddu`, the Jacobian
addition that leaves the doubling out (PR 35). `make_group` writes the group
law once over a layout's `Field`: the sublane bundle ((16, B) arrays; the
add and reduce kernels') and the limb-tile bundle (16 arrays a field element;
the two ladders'). All are plain jnp functions, so they run here eagerly, outside any
`pallas_call` and outside the interpreter (the whole ladder through the
interpreter is tests/test_pallas_kernels.py), against Python integers and
`refimpl`, and the two layouts against each other byte for byte. And the
invariant the kernel rests on: every table `elgamal.FixedBase` makes is
affine."""
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
    """(16, B) array, or a list of 16 (B,) limbs -> ints."""
    return [int(v) for v in F.to_int(np.stack(
        [np.asarray(limb) for limb in tile]).T)]


def _points(pts):
    """[(X, Y, Z) ints] -> the three coordinates' tiles, one lane each."""
    return tuple(_tile([pt[c] for pt in pts]) for c in range(3))


def _limbs(pt):
    """A layout's point -> (3, 16, B) numpy, whichever layout made it."""
    return np.stack([np.stack([np.asarray(limb) for limb in coord])
                     for coord in pt])


# layout -> its Field; both take and give field elements that `_tile` makes
# and `_ints` reads (the limb-tile functions index the major axis only)
FIELDS = {
    "sublane": lambda: po.sublane_field(jnp.asarray(po._M_FP[:, None]),
                                        po._NPRIME_FP),
    "tile": lambda: po.TILE_FIELD,
}


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


def _pmadd(layout, p, x2, y2, z2):
    with jax.enable_x64(False):
        field = FIELDS[layout]()
        return po.make_group(field).pmadd(p, x2, y2, field.is_zero(z2))


@pytest.fixture(scope="module", params=list(FIELDS))
def madd_lanes(request):
    """One eager call of a layout's mixed addition, one lane a case."""
    accs = [_jacobian(a, z) for a, z, _ in CASES.values()]
    ents = [_jacobian(q, 1) for _, _, q in CASES.values()]
    p = _points(accs)
    out = _pmadd(request.param, p, *_points(ents))
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


def test_layouts_agree_byte_for_byte():
    """The limb-tile mixed addition against the sublane one on random
    lanes, infinity planted on either side and on both: every limb equal,
    so the ladder's output bytes are the parent's."""
    n = 24
    accs = [_jacobian(refimpl.g1_mul(refimpl.G1, _rand(N)), _rand(P))
            for _ in range(n)]
    ents = [_jacobian(refimpl.g1_mul(refimpl.G1, _rand(N)), 1)
            for _ in range(n)]
    inf = _jacobian(None, 0)
    accs[1] = accs[3] = inf
    ents[2] = ents[3] = inf
    ents[4] = (_rand(P), _rand(P), 0)       # infinity with arbitrary x, y
    p, q = _points(accs), _points(ents)
    sub = _limbs(_pmadd("sublane", p, *q))
    tile = _limbs(_pmadd("tile", p, *q))
    assert sub.shape == tile.shape == (3, params.NUM_LIMBS, n)
    assert (sub == tile).all()


def _group_add(layout, which, p, q):
    """`padd` or `paddu` of a layout, eagerly."""
    with jax.enable_x64(False):
        return getattr(po.make_group(FIELDS[layout]()), which)(p, q)


# name -> (p affine, its Z, q affine, its Z): what a variable-base ladder's
# window step can meet, which is everything but q == p
ADDU_CASES = {
    "distinct": (A, _rand(P), B_, _rand(P)),
    "distinct_2": (C_, 1, A, _rand(P)),
    "p_at_infinity": (None, 0, B_, _rand(P)),
    "q_at_infinity": (A, _rand(P), None, 0),
    "both_at_infinity": (None, 0, None, 0),
    "opposite_points": (A, _rand(P), NEG_A, _rand(P)),
}


@pytest.fixture(scope="module", params=list(FIELDS))
def addu_lanes(request):
    """One eager call of a layout's `paddu`, one lane a case."""
    ps = [_jacobian(a, z) for a, z, _, _ in ADDU_CASES.values()]
    qs = [_jacobian(b, z) for _, _, b, z in ADDU_CASES.values()]
    out = _group_add(request.param, "paddu", _points(ps), _points(qs))
    return ps, qs, tuple(_ints(t) for t in out)


@pytest.mark.parametrize("case", list(ADDU_CASES))
def test_unequal_addition_matches_oracle(addu_lanes, case):
    ps, qs, out = addu_lanes
    lane = list(ADDU_CASES).index(case)
    a, _, b, _ = ADDU_CASES[case]
    got = tuple(c[lane] for c in out)
    assert all(v < P for v in got)
    assert _affine(*got) == refimpl.g1_add(a, b)
    if a is None:
        assert got == qs[lane]          # the addend, untouched
    elif b is None:
        assert got == ps[lane]          # the accumulator, untouched


@pytest.mark.parametrize("layout", list(FIELDS))
def test_unequal_addition_is_the_complete_one_on_unequal_operands(layout):
    """Limb for limb `padd`'s answer wherever the operands differ: random
    finite lanes, infinity planted on either side and on both (opposite
    operands give Z3 == 0 in both, with other X3, Y3: the oracle's case
    above). And the tile layout's bytes are the sublane layout's."""
    n = 12
    ps = [_jacobian(refimpl.g1_mul(refimpl.G1, _rand(N)), _rand(P))
          for _ in range(n)]
    qs = [_jacobian(refimpl.g1_mul(refimpl.G1, _rand(N)), _rand(P))
          for _ in range(n)]
    inf = _jacobian(None, 0)
    ps[1] = ps[3] = inf
    qs[2] = qs[3] = inf
    qs[4] = (_rand(P), _rand(P), 0)         # infinity with arbitrary x, y
    p, q = _points(ps), _points(qs)
    fast = _limbs(_group_add(layout, "paddu", p, q))
    assert fast.shape == (3, params.NUM_LIMBS, n)
    assert (fast == _limbs(_group_add(layout, "padd", p, q))).all()
    if layout == "tile":
        assert (fast == _limbs(_group_add("sublane", "paddu", p, q))).all()


# operands whose sum or product needs the final subtraction, and whose
# Montgomery reduction ends with a carry out of the top limb (`top > 0`):
# 2^256 - 1 is no normalized input, and both layouts take it alike
EDGES = [0, 1, 2, P - 1, P - 2, (P + 1) // 2, (P - 1) // 2, R % P,
         2 ** 254 - 1, 2 ** 256 - 1]
OPS = {
    "mont_mul": (lambda f: f.mul,
                 lambda a, b: a * b * pow(R, -1, P) % P),
    "fadd": (lambda f: f.add, lambda a, b: (a + b) % P),
    "fsub": (lambda f: f.sub, lambda a, b: (a - b) % P),
}


@pytest.mark.parametrize("op", list(OPS))
def test_limb_tile_field_matches_integers(op):
    pick, want = OPS[op]
    a = [x for x in EDGES for _ in EDGES] + [_rand(P) for _ in range(156)]
    b = [y for _ in EDGES for y in EDGES] + [_rand(P) for _ in range(156)]
    with jax.enable_x64(False):
        got = pick(po.TILE_FIELD)(_tile(a), _tile(b))
        ref = pick(FIELDS["sublane"]())(_tile(a), _tile(b))
    got = _ints(got)
    assert got == _ints(ref)
    for i, (x, y) in enumerate(zip(a, b)):
        if x < P and y < P:
            assert got[i] == want(x, y), (hex(x), hex(y))


@pytest.mark.parametrize("layout", list(FIELDS))
@pytest.mark.parametrize("k", [0, 1, N - 1, N, N + 1, 2 ** 256 - 1],
                         ids=["0", "1", "n-1", "n", "n+1", "2^256-1"])
def test_scalar_made_canonical(k, layout):
    with jax.enable_x64(False):
        if layout == "tile":
            got = po.tile_canonical_scalar(_tile([k, 5]))
        else:
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
