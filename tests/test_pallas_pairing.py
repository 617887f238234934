"""Pallas pairing kernels vs the jnp pairing + pure-Python oracle
(interpret mode on CPU; on the chip the same kernels are checked against
the oracle by scripts/pallas_parity.py).

Covers: Fp12 mul/inv/pow kernels, the ate Miller kernel (up to the free
Fp2 line scales — compared after final exponentiation), and the full
reduced pairing against refimpl.pair.
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from drynx_tpu.crypto import fp2 as F2
from drynx_tpu.crypto import fp12 as F12
from drynx_tpu.crypto import field as F
from drynx_tpu.crypto import pallas_ops as po
from drynx_tpu.crypto import pallas_pairing as pp
from drynx_tpu.crypto import params, refimpl

# Interpreting the pairing kernels on CPU compiles for >40 min on this
# one-core box (same reason the ladder kernels are opt-in,
# tests/test_pallas_kernels.py:16); they are validated on hardware.
pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(
        os.environ.get("DRYNX_PALLAS_INTERPRET_TESTS", "0") != "1",
        reason="pairing-kernel interpret compile is ~1h on CPU; verified "
               "on TPU by scripts/pallas_parity.py"),
]

RNG = np.random.default_rng(23)


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    # INTERPRET is threaded through as a static arg / per-mode jit key
    # (batching._trace_mode), so interpret-mode traces cannot leak into
    # later tests — no cache-clearing teardown needed.
    monkeypatch.setattr(po, "INTERPRET", True)
    monkeypatch.setattr(pp, "INTERPRET", True)


def rfp():
    return int.from_bytes(RNG.bytes(40), "little") % params.P


def rf12():
    return tuple((rfp(), rfp()) for _ in range(6))


def test_f12_mul_inv_pow_kernels():
    a, b = rf12(), rf12()
    da = jnp.asarray(F12.from_ref(a))[None]
    db = jnp.asarray(F12.from_ref(b))[None]
    assert F12.to_ref(pp.f12_mul_flat(da, db)[0]) == refimpl.fp12_mul(a, b)
    inv = pp.f12_inv_flat(da)
    assert refimpl.fp12_mul(F12.to_ref(inv[0]), a) == refimpl.FP12_ONE

    e = 0xABCDEF123456
    k = jnp.asarray(F.from_int(e))[None]
    got = pp.f12_pow_flat(da, k, n_bits=48)
    assert F12.to_ref(got[0]) == refimpl.fp12_pow(a, e)


def test_pair_kernel_matches_oracle():
    P1 = refimpl.g1_mul(refimpl.G1, 5)
    Q1 = refimpl.g2_mul(refimpl.G2, 9)
    xp = jnp.asarray(F.from_int(P1[0] * params.R % params.P))[None]
    yp = jnp.asarray(F.from_int(P1[1] * params.R % params.P))[None]
    xq = jnp.asarray(F2.from_ref(Q1[0]))[None]
    yq = jnp.asarray(F2.from_ref(Q1[1]))[None]

    want = refimpl.pair(P1, Q1)
    # Miller value differs from the jnp one only by free Fp2 line scales:
    # compare after the final exponentiation
    gm = pp.miller_flat(xp, yp, xq, yq)
    assert refimpl.final_exp(F12.to_ref(gm[0])) == want

    got = pp.pair_flat(xp, yp, xq, yq)
    assert F12.to_ref(got[0]) == want


def test_wpow_kernel_matches_oracle():
    a = rf12()
    da = jnp.asarray(F12.from_ref(a))[None]
    e = 0xBEEF1234
    k = jnp.asarray(F.from_int(e))[None]
    got = pp.f12_wpow_flat(da, k, n_bits=32)
    assert F12.to_ref(got[0]) == refimpl.fp12_pow(a, e)


def test_mulreduce8_and_fixed_base_pow():
    vals = [rf12() for _ in range(8)]
    g = jnp.asarray(np.stack([F12.from_ref(v) for v in vals]))[None]
    got = pp.f12_mulreduce8_flat(g)
    want = vals[0]
    for v in vals[1:]:
        want = refimpl.fp12_mul(want, v)
    assert F12.to_ref(got[0]) == want

    from drynx_tpu.proofs import range_proof as rp
    tab = rp.gt_base_table()
    gtb = refimpl.pair(refimpl.G1, refimpl.G2)
    e = int.from_bytes(RNG.bytes(20), "little")
    k = jnp.asarray(F.from_int(e))[None]
    got = pp.gt_pow_fixed(tab, k)
    assert F12.to_ref(got[0]) == refimpl.fp12_pow(gtb, e)


def test_csqr_kernel_matches_generic_square_on_cyclotomic():
    """Granger-Scott cyclotomic squaring == generic squaring on GΦ12
    elements (pairing outputs); also via the wpow cyc=True chain."""
    f = refimpl.pair(refimpl.G1, refimpl.G2)
    df = jnp.asarray(F12.from_ref(f))[None]
    got = pp.f12_csqr_flat(df)
    assert F12.to_ref(got[0]) == refimpl.fp12_mul(f, f)

    e = 0xDEADBEEFCAFE
    k = jnp.asarray(F.from_int(e))[None]
    got = pp.f12_wpow_flat(df, k, n_bits=48, cyc=True)
    assert F12.to_ref(got[0]) == refimpl.fp12_pow(f, e)


def test_scalar_mul_kernel_short_windows():
    """n_windows=16 ladder == full ladder for 62-bit scalars (G1)."""
    from drynx_tpu.crypto import curve as C

    k_int = int.from_bytes(RNG.bytes(7), "little")  # < 2^56
    pt = jnp.asarray(C.from_ref(refimpl.G1))[None]
    k = jnp.asarray(F.from_int(k_int))[None]
    full = po.scalar_mul_flat(pt, k)
    short = po.scalar_mul_flat(pt, k, n_windows=16)
    assert bool(np.all(np.asarray(C.eq(full, short))))


def test_gt_pow_fixed_multi_matches_oracle():
    """Creation's multi-base fixed-window pow: gather + mulreduce8 ==
    fp12_pow on the selected base (interpret mode)."""
    from drynx_tpu.crypto import host_oracle as ho

    bases = [refimpl.pair(refimpl.g1_mul(refimpl.G1, i + 2), refimpl.G2)
             for i in range(3)]
    NB = len(bases)
    T = np.empty((NB, 64, 16, 6, 2, 16), np.uint32)
    for b, cur0 in enumerate(bases):
        cur = cur0
        for w in range(64):
            row = refimpl.FP12_ONE
            T[b, w, 0] = ho._fp12_from_ref(row)
            for j in range(1, 16):
                row = refimpl.fp12_mul(row, cur)
                T[b, w, j] = ho._fp12_from_ref(row)
            for _ in range(4):
                cur = refimpl.fp12_sq(cur)
    es = [0x123456789ABCDEF0, 7, int.from_bytes(RNG.bytes(30), "little")]
    idx = jnp.asarray([2, 0, 1], dtype=jnp.int32)
    k = jnp.asarray(np.stack([np.asarray(F.from_int(e % params.N))
                              for e in es]))
    got = pp.gt_pow_fixed_multi(jnp.asarray(T), idx, k)
    for i, e in enumerate(es):
        want = refimpl.fp12_pow(bases[int(idx[i])], e % params.N)
        assert F12.to_ref(got[i]) == want, i
