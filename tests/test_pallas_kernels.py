"""Pallas kernel parity vs the jnp curve layer (interpreter mode on CPU).

On TPU these kernels are the dispatch target of curve.scalar_mul /
elgamal.fixed_base_mul (crypto/pallas_ops.py); here they run through the
Pallas interpreter so the kernel code paths are covered by the CPU suite."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The full ladder kernels take many minutes to compile through the
# interpreter on CPU; they are validated on real TPU by
# scripts/pallas_parity.py. Opt in with DRYNX_PALLAS_INTERPRET_TESTS=1.
# (The fixed-base ladder is tier-1: see `fixed_base_interpreted`.)
heavy = pytest.mark.skipif(
    os.environ.get("DRYNX_PALLAS_INTERPRET_TESTS", "0") != "1",
    reason="ladder-kernel interpret compile is minutes-slow on CPU; "
           "covered on hardware by scripts/pallas_parity.py")

from drynx_tpu.crypto import curve as C
from drynx_tpu.crypto import elgamal as eg
from drynx_tpu.crypto import field as F
from drynx_tpu.crypto import pallas_ops as po
from drynx_tpu.crypto import params, refimpl

RNG = np.random.default_rng(17)


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(po, "INTERPRET", True)


def _rand_points(n):
    ks = [int.from_bytes(RNG.bytes(32), "little") % params.N
          for _ in range(n)]
    pts = [refimpl.g1_mul(refimpl.G1, k) for k in ks]
    return jnp.asarray(C.from_ref_batch(pts)), pts


def _rand_scalars(n):
    ss = [int.from_bytes(RNG.bytes(32), "little") % params.N
          for _ in range(n)]
    return jnp.asarray(F.from_int(ss)), ss


def _assert_points_equal(a, b):
    ax, ay, ai = C.normalize(a)
    bx, by, bi = C.normalize(b)
    assert bool(jnp.all(ai == bi))
    fin = ~np.asarray(ai)
    assert bool(np.all(np.asarray(ax)[fin] == np.asarray(bx)[fin]))
    assert bool(np.all(np.asarray(ay)[fin] == np.asarray(by)[fin]))


# What a limb-tile ladder kernel costs the interpreter: the fixed-base
# window step (PR 33) is 31 000 whole-vreg operations on arrays of ONE shape,
# and the CPU compiler's instruction fusion does not come back from merging
# them (a chain of two products compiles in 37 s for one's 5 s; the loop body
# ran half an hour unfinished). So a kernel is compiled ahead of time with
# that one pass off, 75-100 s a (n_windows, tiles) pair of the fixed-base
# kernel on the 8-core sandbox, and a pair is compiled once for every lane
# count, table and test that pads to it: the wrapper's own padding,
# transposes and table flattening run eagerly around it, as
# `_fixed_base_mul_flat` writes them.
_UNFUSED = {}


@pytest.fixture
def unfused_interpreter(monkeypatch):
    """`po.pl.pallas_call` through the Pallas interpreter, compiled with
    the CPU compiler's fusion pass off."""
    real = po.pl.pallas_call

    def pallas_call(kernel, **kw):
        call = real(kernel, **kw)

        def run(*args):
            key = (kernel.__name__, kw["grid"], str(kw["scratch_shapes"]),
                   tuple(a.shape for a in args))
            if key not in _UNFUSED:
                _UNFUSED[key] = jax.jit(call).lower(*args).compile(
                    compiler_options={"xla_disable_hlo_passes": "fusion"})
            return _UNFUSED[key](*args)
        return run

    monkeypatch.setattr(po.pl, "pallas_call", pallas_call)


@pytest.fixture
def fixed_base_interpreted(unfused_interpreter):
    """`run(table, k, n_windows)`: `po._fixed_base_mul_flat`, its kernel
    through the Pallas interpreter."""
    return lambda table, k, n_windows=64: po._fixed_base_mul_flat.__wrapped__(
        table, k, n_windows, True)


def _leave_interpreter(monkeypatch):
    """The kernel alone runs through the interpreter. Its points are read
    back in the mode every other module runs in: `C.normalize` traces the
    inversion `po.available()` selects and jit keeps that trace for the
    shape, so one made here (the Pallas inversion, which the CPU cannot
    lower outside the interpreter) would fail here and then serve every
    later module's normalize of as many points."""
    monkeypatch.setattr(po, "INTERPRET", False)


PUB = refimpl.g1_mul(refimpl.G1, 0xD1CE << 77)
# name -> (the base point, its table)
TABLES = {
    "base": (refimpl.G1, lambda: eg.BASE_TABLE),
    "pub": (PUB, lambda: eg.pub_table(PUB)),
    "infinity": (None, lambda: eg.FixedBase(None)),
}
N_ = params.N
EDGE_SCALARS = [0, 1, N_ - 1, N_, N_ + 1, 2 ** 256 - 1, (8 << 252) + 12345,
                0xF0F0 << 100]


def _mul(base, k):
    return None if base is None else refimpl.g1_mul(base, k % N_)


@pytest.mark.parametrize("table", list(TABLES))
def test_fixed_base_kernel_edges_against_oracle(
        fixed_base_interpreted, monkeypatch, table):
    """All 64 windows over TWO 1 024-lane tiles (1 025 lanes: the second
    tile one lane and padding), against the Python oracle alone: the
    scalars around the group order that the kernel reduces itself, a top
    digit of 8 (n's own: the last window whose addend could meet the
    accumulator if k were not below n), zero digits low and high, at the
    first tile's start and across the tiles' seam; random scalars between;
    the generator's table, a public key's and the point at infinity's."""
    base, make = TABLES[table]
    ks = {i: k for i, k in enumerate(EDGE_SCALARS)}
    ks.update({po.TILE_LANES - 4 + i: k
               for i, k in enumerate(EDGE_SCALARS[2:7])})
    ks.update({i: int.from_bytes(RNG.bytes(32), "little")
               for i in (8, 9, 127, 128, 500)})
    k = np.zeros((po.TILE_LANES + 1, params.NUM_LIMBS), np.uint32)
    k[sorted(ks)] = F.from_int([ks[i] for i in sorted(ks)])
    out = fixed_base_interpreted(make().table, jnp.asarray(k))
    assert out.shape == (po.TILE_LANES + 1, 3, params.NUM_LIMBS)
    if base is None:
        assert not np.asarray(out)[:, 2].any()
        return
    _leave_interpreter(monkeypatch)
    got = C.to_ref(out[np.asarray(sorted(ks))])
    assert got == [_mul(base, ks[i]) for i in sorted(ks)]
    rest = np.delete(np.asarray(out), sorted(ks), axis=0)
    assert not rest[:, 2].any()               # the zero scalars between


@pytest.mark.parametrize("table", ["base", "pub"])
@pytest.mark.parametrize("lanes", [1, 5, 129, po.TILE_LANES])
def test_fixed_base_kernel_short_ladder_pads_to_a_tile(
        fixed_base_interpreted, monkeypatch, lanes, table):
    """16 windows (scalars below 16^16: the small plaintexts' ladder) at
    lane counts that all pad to one tile, against the jnp ladder and, on
    eight lanes, the oracle."""
    base, make = TABLES[table]
    small = [0, 1, 15, 16, 200, 16 ** 16 - 1, 0x1234567890ABCDEF, 16 ** 15]
    ss = (small + [int.from_bytes(RNG.bytes(8), "little")
                   for _ in range(lanes)])[:lanes]
    k = jnp.asarray(F.from_int(ss))
    out = fixed_base_interpreted(make().table, k, n_windows=16)
    assert out.shape == (lanes, 3, params.NUM_LIMBS)
    _leave_interpreter(monkeypatch)
    _assert_points_equal(
        out, eg._fixed_base_mul_jnp(make().table, k, n_windows=16))
    assert C.to_ref(out[:8]) == [_mul(base, s) for s in ss[:8]]


class Ref:
    """An array that stands for a kernel's ref: every read and write is
    of one whole limb (eight lanes here: the limb-tile functions take any
    shape), at static or traced-then-concrete indices."""

    def __init__(self, a):
        self.a = np.array(a)
        self.shape = self.a.shape

    @staticmethod
    def _at(i):
        return tuple(int(x) for x in (i if isinstance(i, tuple) else (i,)))

    def __getitem__(self, i):
        return jnp.asarray(self.a[self._at(i)])

    def __setitem__(self, i, v):
        self.a[self._at(i)] = np.asarray(v)


def _python_loop(lo, hi, body, carry):
    for i in range(int(lo), int(hi)):
        carry = body(jnp.int32(i), carry)
    return carry


def _eagerly(kernel, *refs):
    """A kernel's body as a plain function, its `fori_loop`s Python loops
    (under `jax.disable_jit` every jnp operation would leave the dispatch
    fast path too: four times the seconds)."""
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setattr(jax.lax, "fori_loop", _python_loop)
        kernel(*refs)


def test_fixed_base_kernel_two_windows(monkeypatch):
    """The kernel's body as a plain function on arrays that stand for its
    refs, two windows, eagerly: the digit order, the table's slice and its
    flat layout at the smallest `n_windows`, which the interpreter would
    compile a third time for."""
    ss = [0, 1, 15, 16, 17, 200, 255, 0xF0]
    out = Ref(np.zeros((3, params.NUM_LIMBS, len(ss)), np.uint32))
    _eagerly(po._fixed_base_kernel,
             Ref(po._flat_table(eg.BASE_TABLE.table[:2])),
             Ref(F.from_int(ss).T), out,
             Ref(np.zeros((2, len(ss)), np.uint32)))
    _leave_interpreter(monkeypatch)
    got = C.to_ref(jnp.asarray(out.a.transpose(2, 0, 1)))
    assert got == [_mul(refimpl.G1, s) for s in ss]


# lane -> (scalar, whether its base is the point at infinity): the
# variable-base kernel's two-window body sees (k mod n) mod 256
TWO_WINDOWS = {
    "0": (0, False), "1": (1, False), "15": (15, False), "16": (16, False),
    "17": (17, False), "255": (255, False), "0xF0": (0xF0, False),
    "base_at_infinity": (0x35, True),
    "n-1": (N_ - 1, False), "n": (N_, False), "n+1": (N_ + 1, False),
    "2^256-1": (2 ** 256 - 1, False),
}


@pytest.fixture(scope="module")
def scalar_mul_two_windows():
    """`po._scalar_mul_kernel`'s body as a plain function on arrays that
    stand for its refs, ONE eager run (the table loop's 161 products and a
    window's 44: some 15 s), every lane its own base."""
    ks = [k for k, _ in TWO_WINDOWS.values()]
    bases = [None if inf else refimpl.g1_mul(refimpl.G1, 0xBA5E + 77 * i)
             for i, (_, inf) in enumerate(TWO_WINDOWS.values())]
    n = len(ks)
    p = np.asarray(C.from_ref_batch(bases)).transpose(1, 2, 0)
    out = Ref(np.zeros((3, params.NUM_LIMBS, n), np.uint32))
    tab = Ref(np.zeros((15, 3, params.NUM_LIMBS, n), np.uint32))
    dig = Ref(np.zeros((2, n), np.uint32))
    _eagerly(po._scalar_mul_kernel, Ref(p), Ref(F.from_int(ks).T), out, tab,
             dig)
    return bases, dig.a, tab.a, out.a


def _to_ref(pt):
    """(3, 16) Montgomery Jacobian limbs -> affine ints or None, in Python
    integers alone."""
    rinv = pow(params.R, -1, params.P)
    x, y, z = (int(v) * rinv % params.P for v in F.to_int(np.asarray(pt)))
    if z == 0:
        return None
    zi = pow(z, -1, params.P)
    return x * zi * zi % params.P, y * zi ** 3 % params.P


@pytest.mark.parametrize("lane", list(TWO_WINDOWS))
def test_scalar_mul_kernel_two_windows(scalar_mul_two_windows, lane):
    """Digits MSB first, of the scalar made canonical FIRST (n - 1, n,
    n + 1, 2^256 - 1: the low byte of k mod n, not of k), the table
    d * P at d - 1 for d in 1..15, and the window's 4 doublings and one
    addition, against the Python oracle; a base at infinity gives
    infinity."""
    bases, dig, tab, out = scalar_mul_two_windows
    i = list(TWO_WINDOWS).index(lane)
    k, _ = TWO_WINDOWS[lane]
    low = k % N_ % 256
    assert (int(dig[0, i]), int(dig[1, i])) == divmod(low, 16)
    assert _to_ref(out[:, :, i]) == _mul(bases[i], low)
    for d in (1, 2, 3, 8, 15):
        assert _to_ref(tab[d - 1, :, :, i]) == _mul(bases[i], d), d


@heavy
@pytest.mark.slow(reason="609 s on the 8-core sandbox (PR 35): the "
                  "variable-base ladder's 140 000 operations through the "
                  "interpreter, the fusion pass off")
def test_scalar_mul_kernel_matches_jnp(unfused_interpreter, monkeypatch):
    """All 64 windows of the variable-base kernel through the interpreter,
    one tile: zero, one, the scalars around the group order, a base at
    infinity, random scalars, against the jnp ladder and the oracle."""
    ss = [0, 1, N_ - 1, N_, N_ + 1, 2 ** 256 - 1, (8 << 252) + 12345] + [
        int.from_bytes(RNG.bytes(32), "little") for _ in range(3)]
    p, pts = _rand_points(len(ss))
    p = p.at[4].set(C.infinity())
    pts[4] = None
    k = jnp.asarray(F.from_int(ss))
    out = po._scalar_mul_flat.__wrapped__(p, k, 64, True)
    assert out.shape == (len(ss), 3, params.NUM_LIMBS)
    _leave_interpreter(monkeypatch)
    assert C.to_ref(out) == [_mul(b, s) for b, s in zip(pts, ss)]
    reduced = jnp.asarray(F.from_int([s % N_ for s in ss]))
    _assert_points_equal(out, C._scalar_mul_jnp(p, reduced))


@heavy
def test_point_add_and_reduce_kernels():
    n = 3
    p, _ = _rand_points(n)
    q, _ = _rand_points(n)
    _assert_points_equal(po.point_add_flat(p, q), C.add(p, q))

    stack = jnp.stack([p, q, C.neg(p)])       # (3, n, 3, 16)
    want = C.add(C.add(p, q), C.neg(p))       # == q
    _assert_points_equal(po.point_reduce_flat(stack), want)
