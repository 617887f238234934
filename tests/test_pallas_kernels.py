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
# (The fixed-base ladder's take 47-49 s each since its window step is the
# mixed addition; one test of it, against the oracle alone, is tier-1.)
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


@heavy
def test_scalar_mul_kernel_matches_jnp():
    n = 4
    p, _ = _rand_points(n)
    k, _ = _rand_scalars(n)
    k = k.at[0].set(0)  # edge: zero scalar -> infinity
    out_pallas = po.scalar_mul_flat(p, k)
    out_jnp = C._scalar_mul_jnp(p, k)
    _assert_points_equal(out_pallas, out_jnp)


@heavy
def test_fixed_base_kernel_matches_jnp():
    n = 5
    k, ss = _rand_scalars(n)
    # edges of the mixed-addition ladder: the largest scalar, and a top
    # digit of 8 (n's own: the last window whose addend could meet the
    # accumulator if k were not below n)
    ss[2], ss[3] = params.N - 1, (8 << 252) + 12345
    k = jnp.asarray(F.from_int(ss))
    out_pallas = po.fixed_base_mul_flat(eg.BASE_TABLE.table, k)
    out_jnp = eg._fixed_base_mul_jnp(eg.BASE_TABLE.table, k)
    _assert_points_equal(out_pallas, out_jnp)
    assert C.to_ref(out_pallas[1]) == refimpl.g1_mul(refimpl.G1, ss[1])


def test_fixed_base_kernel_edges_against_oracle(monkeypatch):
    """The whole 64-window kernel through the interpreter (26 s of
    compile on the 8-core sandbox since its window step is the mixed
    addition), against the Python oracle alone: the scalars around the
    group order that the kernel reduces itself, a top digit of 8, zero
    digits low and high, and the table of the point at infinity."""
    n = params.N
    ks = [0, 1, n - 1, n, n + 1, 2 ** 256 - 1, (8 << 252) + 12345,
          0xF0F0 << 100]
    k = jnp.asarray(F.from_int(ks))
    out = po.fixed_base_mul_flat(eg.BASE_TABLE.table, k)
    of_infinity = po.fixed_base_mul_flat(eg.FixedBase(None).table, k)
    assert not np.asarray(of_infinity)[:, 2].any()
    # The kernel alone runs through the interpreter. Its points are read
    # back in the mode every other module runs in: `C.normalize` traces the
    # inversion `po.available()` selects and jit keeps that trace for the
    # shape, so one made here (the Pallas inversion, which the CPU cannot
    # lower outside the interpreter) would fail here and then serve every
    # later module's normalize of eight points.
    monkeypatch.setattr(po, "INTERPRET", False)
    assert C.to_ref(out) == [refimpl.g1_mul(refimpl.G1, s) for s in ks]


@heavy
def test_fixed_base_ladder_small_always_on():
    """Formerly always-on slice of the ladder kernel (n_windows=2): measured
    in round 4, even this truncated interpret compile runs tens of minutes
    on this box under jax 0.8, so it joins the opt-in interpret tier — the
    kernels are validated on hardware (scripts/pallas_parity.py) and the
    digit/table/padd logic is oracle-tested at the jnp layer."""
    ss = [0, 1, 200]  # infinity edge + generator + 2-digit scalar
    k = jnp.asarray(F.from_int(ss))
    out_pallas = po.fixed_base_mul_flat(eg.BASE_TABLE.table, k, n_windows=2)
    out_jnp = eg._fixed_base_mul_jnp(eg.BASE_TABLE.table, k, n_windows=2)
    _assert_points_equal(out_pallas, out_jnp)
    assert C.to_ref(out_pallas[2]) == refimpl.g1_mul(refimpl.G1, 200)


@heavy
def test_point_add_and_reduce_kernels():
    n = 3
    p, _ = _rand_points(n)
    q, _ = _rand_points(n)
    _assert_points_equal(po.point_add_flat(p, q), C.add(p, q))

    stack = jnp.stack([p, q, C.neg(p)])       # (3, n, 3, 16)
    want = C.add(C.add(p, q), C.neg(p))       # == q
    _assert_points_equal(po.point_reduce_flat(stack), want)
