"""Pallas TPU kernels for the pairing pipeline (optimal ate + final exp).

Why: the jnp pairing (crypto/pairing.py) is correct but its rolled limb
loops execute as nested XLA while-loops — ~2.7-5.5s per Miller batch on the
chip regardless of batch size (loop overhead, not compute). Range-proof
creation/verification dispatches tens of thousands of pairings (reference
cost center: lib/range/range_proof.go:504-565, 21.7 s VN phase), so the
pairing must run like the scalar-mul ladders: whole loop inside one Mosaic
kernel, limbs on sublanes, batch on lanes (see crypto/pallas_ops.py).

Kernels:
  miller_flat(p, q)        optimal ate Miller function, batched
  f12_mul_flat(a, b)       one Fp12 product (final-exp glue)
  f12_inv_flat(f)          Fp12 inversion (tower + in-kernel Fermat Fp inv)
  f12_pow_flat(f, k, n)    f^k, square-and-multiply-always over n bit rows
  pair_flat(px, py, qx, qy)  full reduced pairing (miller + final exp),
                           final-exp Frobenius/Olivos glue at jnp level

Math mirrors crypto/pairing.py exactly (same line sparsity {0,1,3}, same
DSD/Olivos hard part); parity is asserted against it in
tests/test_pallas_pairing.py via interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import params
from .pallas_ops import (INTERPRET, LANES, MASK, NL, _M_FP, _NPRIME_FP,
                         _pad_lanes, fadd, fsub, mont_mul)

_XI_A = params.XI[0]          # XI = (3, 1): (x0+x1 i)(3+i)
assert params.XI[1] == 1

_ATE_BITS = [int(b) for b in bin(6 * params.U + 2)[3:]]   # MSB-first, 65
_U_BITS_LSB = [(params.U >> i) & 1 for i in range(params.U.bit_length())]
_PM2_BITS = [int(b) for b in bin(params.P - 2)[2:]]       # MSB-first


def _fermat_inv(x, mul):
    """x^(p-2) via the static square-and-multiply chain — trace-time
    UNROLLED (~383 inlined Montgomery muls). Kept only as the fallback for
    make_fp12 callers that pass no bit rows; the inversion kernels use
    _fermat_inv_rolled, whose jaxpr is two muls in a fori_loop body."""
    acc = x
    for bit in _PM2_BITS[1:]:
        acc = mul(acc, acc)
        if bit:
            acc = mul(acc, x)
    return acc


def _fermat_inv_rolled(x, mul, bits_ref):
    """x^(p-2) as a fori_loop over pre-staged bit rows of p-2 (MSB-first,
    bits_ref (256, B) — broadcast host-side like the Miller ate bits;
    in-kernel constant broadcasts hit the unimplemented Mosaic
    sublane+lane path). Square-and-multiply-ALWAYS with a per-lane select:
    ~127 extra Fp muls per chain, but the traced body is 2 muls instead of
    ~383 — the unrolled chain was the dominant jaxpr cost of every
    inversion kernel (and the r05 C-stack overflow food)."""
    def body(w, acc):
        acc = mul(acc, acc)
        bit = bits_ref[pl.ds(w, 1), :][0]        # (B,)
        accm = mul(acc, x)
        return jnp.where((bit == 1)[None, :], accm, acc)

    return jax.lax.fori_loop(jnp.int32(1), jnp.int32(len(_PM2_BITS)),
                             body, x)


def _pm2_bits_tiles() -> np.ndarray:
    """(256, LANES) uint32: MSB-first bits of p-2, lane-broadcast."""
    return np.broadcast_to(
        np.asarray(_PM2_BITS, dtype=np.uint32)[:, None],
        (len(_PM2_BITS), LANES)).copy()


# ---------------------------------------------------------------------------
# In-kernel Fp2 / Fp12 arithmetic on (16, B) limb tiles
# ---------------------------------------------------------------------------

def make_fp2(m, nprime):
    mul = lambda a, b: mont_mul(a, b, m, nprime)
    add = lambda a, b: fadd(a, b, m)
    sub = lambda a, b: fsub(a, b, m)

    def f2add(a, b):
        return (add(a[0], b[0]), add(a[1], b[1]))

    def f2sub(a, b):
        return (sub(a[0], b[0]), sub(a[1], b[1]))

    def f2neg(a):
        z = jnp.zeros_like(a[0])
        return (sub(z, a[0]), sub(z, a[1]))

    def f2conj(a):
        z = jnp.zeros_like(a[1])
        return (a[0], sub(z, a[1]))

    def f2mul(a, b):
        # Karatsuba over i^2 = -1: 3 Montgomery muls
        t0 = mul(a[0], b[0])
        t1 = mul(a[1], b[1])
        t2 = mul(add(a[0], a[1]), add(b[0], b[1]))
        return (sub(t0, t1), sub(sub(t2, t0), t1))

    def f2sqr(a):
        re = mul(add(a[0], a[1]), sub(a[0], a[1]))
        im2 = mul(a[0], a[1])
        return (re, add(im2, im2))

    def f2mul_fp(a, s):
        return (mul(a[0], s), mul(a[1], s))

    def _mul3(x):
        return add(add(x, x), x)

    def f2mul_xi(a):
        # (x0 + x1 i)(3 + i) = (3x0 - x1) + (x0 + 3x1) i
        return (sub(_mul3(a[0]), a[1]), add(a[0], _mul3(a[1])))

    return dict(add=f2add, sub=f2sub, neg=f2neg, conj=f2conj, mul=f2mul,
                sqr=f2sqr, mul_fp=f2mul_fp, mul_xi=f2mul_xi,
                fp_mul=mul, fp_add=add, fp_sub=sub)


def make_fp12(F2, pm2_bits_ref=None):
    """Fp12 = 6-list of Fp2 pairs; flat tower w^6 = XI (crypto/fp12.py).

    pm2_bits_ref: optional (256, B) bit rows of p-2 (see _pm2_bits_tiles).
    When given, the Fermat Fp inversion inside the tower runs as a rolled
    fori_loop (tiny jaxpr); without it the unrolled chain is used — only
    the inversion kernel actually reaches fp_inv, and it passes the rows.

    Multiplication runs over the Fp6 sub-tower (v = w^2, v^3 = XI;
    f = A(v) + w*B(v) with A = (f0,f2,f4), B = (f1,f3,f5)):
    Karatsuba at both levels gives 3*6 = 18 Fp2 muls per full product
    (vs 36 schoolbook) and 12 per squaring — the pairing/pow kernels are
    Fp2-mul-bound, so this is a direct ~2x on every GT-heavy op.
    """

    # Fp6 helpers on Fp2 triples (crypto/fp12.py:66-110)
    def fp6_mul(a, b):
        # 3-way Karatsuba: 6 Fp2 muls
        t0 = F2["mul"](a[0], b[0])
        t1 = F2["mul"](a[1], b[1])
        t2 = F2["mul"](a[2], b[2])
        m01 = F2["mul"](F2["add"](a[0], a[1]), F2["add"](b[0], b[1]))
        m02 = F2["mul"](F2["add"](a[0], a[2]), F2["add"](b[0], b[2]))
        m12 = F2["mul"](F2["add"](a[1], a[2]), F2["add"](b[1], b[2]))
        c0 = F2["add"](t0, F2["mul_xi"](F2["sub"](F2["sub"](m12, t1), t2)))
        c1 = F2["add"](F2["sub"](F2["sub"](m01, t0), t1), F2["mul_xi"](t2))
        c2 = F2["add"](F2["sub"](F2["sub"](m02, t0), t2), t1)
        return (c0, c1, c2)

    def fp6_add(a, b):
        return tuple(F2["add"](x, y) for x, y in zip(a, b))

    def _split(f):
        return (f[0], f[2], f[4]), (f[1], f[3], f[5])

    def _join(A, B):
        return [A[0], B[0], A[1], B[1], A[2], B[2]]

    def f12mul(a, b):
        A1, B1 = _split(a)
        A2, B2 = _split(b)
        t0 = fp6_mul(A1, A2)
        t1 = fp6_mul(B1, B2)
        t2 = fp6_mul(fp6_add(A1, B1), fp6_add(A2, B2))
        return _join(fp6_add(t0, fp6_mul_v(t1)),
                     fp6_sub(fp6_sub(t2, t0), t1))

    def f12sqr(a):
        # complex-method squaring over Fp6: 2 Fp6 muls = 12 Fp2 muls
        A, B = _split(a)
        ab = fp6_mul(A, B)
        t = fp6_mul(fp6_add(A, B), fp6_add(A, fp6_mul_v(B)))
        c0 = fp6_sub(fp6_sub(t, ab), fp6_mul_v(ab))
        return _join(c0, fp6_add(ab, ab))

    def f12csqr(f):
        """Granger-Scott cyclotomic squaring (eprint 2009/565 §3.2): valid
        ONLY for f in GΦ12(p) — i.e. f^(p^4-p^2+1) = 1, which holds for
        every pairing output after the final exponentiation. 9 Fp2
        squarings (18 Montgomery muls) vs 12 Fp2 muls (36) for the complex
        method — 2x on every squaring in a GT pow chain. Formulas validated
        against the refimpl oracle on the flat tower basis (f_k w^k,
        w^6 = XI): gnark-style coords x0..x5 = f0, f2, f4, f1, f3, f5."""
        f0, f1, f2, f3, f4, f5 = f
        t0 = F2["sqr"](f3)
        t1 = F2["sqr"](f0)
        t6 = F2["sub"](F2["sub"](F2["sqr"](F2["add"](f3, f0)), t0), t1)
        t2 = F2["sqr"](f4)
        t3 = F2["sqr"](f1)
        t7 = F2["sub"](F2["sub"](F2["sqr"](F2["add"](f4, f1)), t2), t3)
        t4 = F2["sqr"](f5)
        t5 = F2["sqr"](f2)
        t8 = F2["mul_xi"](
            F2["sub"](F2["sub"](F2["sqr"](F2["add"](f5, f2)), t4), t5))
        t0 = F2["add"](F2["mul_xi"](t0), t1)
        t2 = F2["add"](F2["mul_xi"](t2), t3)
        t4 = F2["add"](F2["mul_xi"](t4), t5)

        def out_sub(t, x):          # 3t - 2x = 2(t - x) + t
            d = F2["sub"](t, x)
            return F2["add"](F2["add"](d, d), t)

        def out_add(t, x):          # 3t + 2x = 2(t + x) + t
            s = F2["add"](t, x)
            return F2["add"](F2["add"](s, s), t)

        return [out_sub(t0, f0), out_add(t8, f1), out_sub(t2, f2),
                out_add(t6, f3), out_sub(t4, f4), out_add(t7, f5)]

    def f12conj6(a):
        return [a[k] if k % 2 == 0 else F2["neg"](a[k]) for k in range(6)]

    def fp6_sub(a, b):
        return tuple(F2["sub"](x, y) for x, y in zip(a, b))

    def fp6_mul_v(a):
        return (F2["mul_xi"](a[2]), a[0], a[1])

    def fp_inv(x):
        if pm2_bits_ref is not None:
            return _fermat_inv_rolled(x, F2["fp_mul"], pm2_bits_ref)
        return _fermat_inv(x, F2["fp_mul"])

    def f2inv(a):
        n = F2["fp_add"](F2["fp_mul"](a[0], a[0]), F2["fp_mul"](a[1], a[1]))
        ni = fp_inv(n)
        z = jnp.zeros_like(a[1])
        return (F2["fp_mul"](a[0], ni),
                F2["fp_mul"](F2["fp_sub"](z, a[1]), ni))

    def fp6_inv(a):
        a0, a1, a2 = a
        c0 = F2["sub"](F2["sqr"](a0), F2["mul_xi"](F2["mul"](a1, a2)))
        c1 = F2["sub"](F2["mul_xi"](F2["sqr"](a2)), F2["mul"](a0, a1))
        c2 = F2["sub"](F2["sqr"](a1), F2["mul"](a0, a2))
        t = F2["add"](F2["mul"](a0, c0), F2["mul_xi"](
            F2["add"](F2["mul"](a1, c2), F2["mul"](a2, c1))))
        ti = f2inv(t)
        return (F2["mul"](c0, ti), F2["mul"](c1, ti), F2["mul"](c2, ti))

    def f12inv(f):
        a = (f[0], f[2], f[4])
        b = (f[1], f[3], f[5])
        norm = fp6_sub(fp6_mul(a, a), fp6_mul_v(fp6_mul(b, b)))
        ninv = fp6_inv(norm)
        ra = fp6_mul(a, ninv)
        rb = fp6_mul(b, ninv)
        rb = tuple(F2["neg"](x) for x in rb)
        return [ra[0], rb[0], ra[1], rb[1], ra[2], rb[2]]

    def sparse013(f, l0, l1, l3):
        acc = [None] * 9

        def accum(k, v):
            acc[k] = v if acc[k] is None else F2["add"](acc[k], v)

        for k in range(6):
            accum(k, F2["mul"](f[k], l0))
            accum(k + 1, F2["mul"](f[k], l1))
            accum(k + 3, F2["mul"](f[k], l3))
        out = list(acc[:6])
        for k in range(6, 9):
            out[k - 6] = F2["add"](out[k - 6], F2["mul_xi"](acc[k]))
        return out

    return dict(mul=f12mul, sqr=f12sqr, csqr=f12csqr, conj6=f12conj6,
                inv=f12inv, sparse013=sparse013)


def _f12_load(ref):
    """(12, 16, B) ref -> 6-list of Fp2 pairs of (16, B)."""
    return [(ref[2 * k], ref[2 * k + 1]) for k in range(6)]


def _f12_store(o_ref, f):
    for k in range(6):
        o_ref[2 * k] = f[k][0]
        o_ref[2 * k + 1] = f[k][1]


def _f12_one_tiles(one_col, B):
    """Fp12 one from a (16, 1) Montgomery-one column (kernel input — Mosaic
    rejects captured host arrays; see pallas_ops module docstring)."""
    rows = [jnp.broadcast_to(one_col, (NL, B))]
    rows += [jnp.zeros((NL, B), jnp.uint32)] * 11
    return [(rows[2 * k], rows[2 * k + 1]) for k in range(6)]


def _f12_select(cond, a, b):
    """Per-lane select between two Fp12 values; cond (B,) bool."""
    c = cond[None, :]
    return [(jnp.where(c, x[0], y[0]), jnp.where(c, x[1], y[1]))
            for x, y in zip(a, b)]


# ---------------------------------------------------------------------------
# Miller loop kernel
# ---------------------------------------------------------------------------

def _miller_kernel(m_ref, np_ref, g_ref, bits_ref, p_ref, q_ref, o_ref):
    """Optimal ate Miller function (mirrors pairing.miller_loop).

    g_ref: (16, 8) — the three G2-Frobenius Fp2 constants (g12, g13, g22)
    as limb columns, then (one_mont, 0). p_ref: (2, 16, B) G1 affine
    Montgomery; q_ref: (10, 16, B): xq, yq, then the host-precomputed Frobenius
    images q1x, q1y, nq2x (each an Fp2 pair of rows).
    """
    m = m_ref[:]
    nprime = np_ref[0, 0]
    F2 = make_fp2(m, nprime)
    F12 = make_fp12(F2)

    B = p_ref.shape[-1]
    xp, yp = p_ref[0], p_ref[1]
    xq = (q_ref[0], q_ref[1])
    yq = (q_ref[2], q_ref[3])
    # Frobenius images of Q are precomputed host-side (constant-broadcast
    # multiplications inside the kernel hit an unimplemented Mosaic
    # sublane+lane broadcast when mixed with trace-level constant folding)
    q1x = (q_ref[4], q_ref[5])
    q1y = (q_ref[6], q_ref[7])
    nq2x = (q_ref[8], q_ref[9])

    # constants live in a 2D (limbs x columns) block; slicing a lane column
    # then broadcasting is the Mosaic-supported pattern (see the fixed-base
    # kernel's table select)
    one_m = jnp.broadcast_to(g_ref[:, 6:7], (NL, B))


    def dbl_step(T, f):
        X, Y, Z = T
        A = F2["sqr"](X)
        Bv = F2["sqr"](Y)
        zz = F2["sqr"](Z)
        E = F2["add"](F2["add"](A, A), A)              # 3X^2
        AX = F2["mul"](A, X)                           # X^3
        l3 = F2["sub"](F2["add"](F2["add"](AX, AX), AX),
                       F2["add"](Bv, Bv))              # 3X^3 - 2Y^2
        l1 = F2["mul_fp"](F2["neg"](F2["mul"](E, zz)), xp)
        YZ = F2["mul"](Y, Z)
        YZ3 = F2["mul"](YZ, zz)
        l0 = F2["mul_fp"](F2["add"](YZ3, YZ3), yp)
        # point double (same formulas as pallas_ops.make_group pdouble)
        Cv = F2["sqr"](Bv)
        t0 = F2["add"](X, Bv)
        t = F2["sub"](F2["sqr"](t0), F2["add"](A, Cv))
        D = F2["add"](t, t)
        Fv = F2["sqr"](E)
        X3 = F2["sub"](Fv, F2["add"](D, D))
        C2 = F2["add"](Cv, Cv)
        C8 = F2["add"](F2["add"](C2, C2), F2["add"](C2, C2))
        Y3 = F2["sub"](F2["mul"](E, F2["sub"](D, X3)), C8)
        Z3 = F2["add"](YZ, YZ)
        f = F12["sqr"](f)
        f = F12["sparse013"](f, l0, l1, l3)
        return (X3, Y3, Z3), f

    def add_step(T, f, qx, qy):
        """Mixed add T + (qx, qy) with the line through them; the whole line
        may be scaled by any Fp2 factor (killed by the final exponentiation),
        so the madd-convention sign flip is free (pairing.py's line times -1:
        l0 = Hm Z yp, l1 = -r1 xp, l3 = r1 xq - Hm Z yq).

        Vertical degeneracy (Hm = 0: x_T == x_Q, possible only on crafted
        wire points) mirrors the jnp miller_loop: line contributes 1 and the
        point update is skipped — TPU and CPU verifiers must agree."""
        X1, Y1, Z1 = T
        zz = F2["sqr"](Z1)
        U2 = F2["mul"](qx, zz)
        S2 = F2["mul"](qy, F2["mul"](Z1, zz))
        Hm = F2["sub"](U2, X1)
        r1 = F2["sub"](S2, Y1)
        HmZ = F2["mul"](Hm, Z1)
        l0 = F2["mul_fp"](HmZ, yp)
        l1 = F2["mul_fp"](F2["neg"](r1), xp)
        l3 = F2["sub"](F2["mul"](r1, qx), F2["mul"](HmZ, qy))
        f2 = F12["sparse013"](f, l0, l1, l3)
        # madd-2007-bl point addition
        HH = F2["sqr"](Hm)
        I4 = F2["add"](F2["add"](HH, HH), F2["add"](HH, HH))
        J = F2["mul"](Hm, I4)
        rm = F2["add"](r1, r1)
        V = F2["mul"](X1, I4)
        X3 = F2["sub"](F2["sub"](F2["sqr"](rm), J), F2["add"](V, V))
        YJ = F2["mul"](Y1, J)
        Y3 = F2["sub"](F2["mul"](rm, F2["sub"](V, X3)), F2["add"](YJ, YJ))
        Z3 = F2["sub"](F2["sub"](F2["sqr"](F2["add"](Z1, Hm)), zz), HH)
        degen = _f2_is_zero(Hm)
        Tn = tuple((jnp.where(degen[None, :], a[0], b[0]),
                    jnp.where(degen[None, :], a[1], b[1]))
                   for a, b in zip(T, (X3, Y3, Z3)))
        fn = _f12_select(degen, f, f2)
        return Tn, fn

    T0 = (xq, yq, (one_m, jnp.zeros((NL, B), jnp.uint32)))
    f0 = _f12_one_tiles(g_ref[:, 6:7], B)

    def body(w, state):
        T, f = state
        T, f = dbl_step(T, f)
        # bits are pre-broadcast to lanes (scalar->tile broadcasts hit an
        # unimplemented Mosaic "broadcast in both sublanes and lanes" path)
        bit = bits_ref[pl.ds(w, 1), :][0]          # (B,)
        Ta, fa = add_step(T, f, xq, yq)
        cond = bit == 1
        T = tuple((jnp.where(cond[None, :], a[0], b[0]),
                   jnp.where(cond[None, :], a[1], b[1]))
                  for a, b in zip(Ta, T))
        f = _f12_select(cond, fa, f)
        return (T, f)

    T, f = jax.lax.fori_loop(jnp.int32(0), jnp.int32(len(_ATE_BITS)), body,
                             (T0, f0))

    # Frobenius corrections: Q1 = (conj(xq)*g12, conj(yq)*g13);
    # -pi^2(Q) = (xq*g22, yq)  [XI non-square => XI^((p^2-1)/2) = -1]
    T, f = add_step(T, f, q1x, q1y)
    _, f = add_step(T, f, nq2x, yq)
    _f12_store(o_ref, f)


def _twist_frob_tiles() -> np.ndarray:
    """(16, 8): columns = g12_0, g12_1, g13_0, g13_1, g22_0, g22_1 (the G2
    Frobenius Fp2 constants), one_mont, 0 — Montgomery limbs on sublanes."""
    from . import refimpl

    cols = []
    for c in (refimpl._G12, refimpl._G13, refimpl._G22):
        for comp in c:
            cols.append(np.asarray(
                params.to_limbs(comp * params.R % params.P), dtype=np.uint32))
    cols.append(np.asarray(params.to_limbs(params.R % params.P),
                           dtype=np.uint32))
    cols.append(np.zeros(NL, dtype=np.uint32))
    return np.stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnames="interpret")
def _miller_flat(px, py, qx, qy, interpret: bool):
    from . import fp2 as F2j

    N = px.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    p_in = _pad_lanes(jnp.stack([px.T, py.T]), Np)            # (2, 16, Np)
    # host-side Frobenius images of Q (refimpl.twist_frob semantics)
    g12, g13, g22 = _twist_frob_consts_jnp()
    q1x = F2j.mul(F2j.conj(qx), g12)
    q1y = F2j.mul(F2j.conj(qy), g13)
    nq2x = F2j.mul(qx, g22)
    q_in = _pad_lanes(jnp.concatenate(
        [jnp.transpose(t, (1, 2, 0))
         for t in (qx, qy, q1x, q1y, nq2x)], axis=0), Np)     # (10, 16, Np)
    m_in = jnp.asarray(_M_FP[:, None], dtype=jnp.uint32)
    np_in = jnp.asarray([[_NPRIME_FP]], dtype=jnp.uint32)
    g_in = jnp.asarray(_twist_frob_tiles(), dtype=jnp.uint32)
    bits_in = jnp.asarray(np.broadcast_to(
        np.asarray(_ATE_BITS, dtype=np.uint32)[:, None],
        (len(_ATE_BITS), LANES)).copy(), dtype=jnp.uint32)

    with jax.enable_x64(False):
        out = pl.pallas_call(
            _miller_kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((NL, 1), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((NL, 8), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((len(_ATE_BITS), LANES), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((2, NL, LANES), lambda i: (0, 0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((10, NL, LANES), lambda i: (0, 0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((12, NL, LANES), lambda i: (0, 0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((12, NL, Np), jnp.uint32),
            interpret=interpret,
        )(m_in, np_in, g_in, bits_in, p_in, q_in)
    return jnp.transpose(out, (2, 0, 1))[:N].reshape(N, 6, 2, NL)


def miller_flat(px, py, qx, qy):
    """Batched ate Miller function.

    px, py: (N, 16) Fp Montgomery; qx, qy: (N, 2, 16) Fp2 Montgomery.
    Returns (N, 6, 2, 16) unreduced Miller value (host layout).
    """
    return _miller_flat(px, py, qx, qy, INTERPRET)


_TF_JNP = None


def _twist_frob_consts_jnp():
    global _TF_JNP
    if _TF_JNP is None:
        from . import fp2 as F2j
        from . import refimpl

        # cache NUMPY (a jnp array materialized inside a jit trace is a
        # tracer — caching it across calls leaks it out of the trace)
        _TF_JNP = tuple(np.asarray(F2j.from_ref(c))
                        for c in (refimpl._G12, refimpl._G13, refimpl._G22))
    return _TF_JNP


# ---------------------------------------------------------------------------
# Fp12 mul / inv / pow kernels (final-exp building blocks + GT ops)
# ---------------------------------------------------------------------------

def _f12_mul_kernel(m_ref, np_ref, a_ref, b_ref, o_ref):
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    F12 = make_fp12(F2)
    _f12_store(o_ref, F12["mul"](_f12_load(a_ref), _f12_load(b_ref)))


def _f12_inv_kernel(m_ref, np_ref, bits_ref, a_ref, o_ref):
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    F12 = make_fp12(F2, pm2_bits_ref=bits_ref)
    _f12_store(o_ref, F12["inv"](_f12_load(a_ref)))


def _f12_pow_kernel(m_ref, np_ref, one_ref, f_ref, k_ref, o_ref, bit_ref,
                    *, n_bits: int):
    """f^k, LSB-first square-and-multiply-always over n_bits bit rows.
    one_ref: (16, 1) Montgomery-one column for the Fp12 identity."""
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    F12 = make_fp12(F2)
    B = f_ref.shape[-1]
    k = k_ref[:]

    rows = []
    for w in range(n_bits):
        limb, s = divmod(w, params.LIMB_BITS)
        rows.append((k[limb] >> np.uint32(s)) & np.uint32(1))
    bit_ref[:] = jnp.stack(rows)                 # (n_bits, B)

    base0 = _f12_load(f_ref)
    acc0 = _f12_one_tiles(one_ref[:], B)

    def body(w, state):
        acc, base = state
        bit = bit_ref[pl.ds(w, 1), :][0]
        acc2 = F12["mul"](acc, base)
        acc = _f12_select(bit == 1, acc2, acc)
        base = F12["sqr"](base)
        return (acc, base)

    acc, _ = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_bits), body,
                               (acc0, base0))
    _f12_store(o_ref, acc)


def _f12_wpow_kernel(m_ref, np_ref, one_ref, f_ref, k_ref, o_ref, dig_ref,
                     *, n_bits: int, wbits: int, cyc: bool = False):
    """f^k via wbits-wide windows, MSB-first: an in-kernel 2^wbits-entry
    power table, then per window `wbits` squarings + one select-mul.
    With sqr = 12 and mul = 18 Fp2 muls this is ~2.4x over the
    square-and-multiply-always _f12_pow_kernel. wbits=3 keeps the live
    table at 8 Fp12 values — 4-bit windows blow the 16 MB scoped-VMEM
    budget (observed OOM at 17.2 MB). one_ref: (16, 1) Montgomery one.

    cyc=True swaps every squaring (window chain AND table build — all
    operands are powers of the base) for the Granger-Scott cyclotomic
    squaring: 2x cheaper, valid only when f ∈ GΦ12(p). Callers must
    guarantee membership (pairing outputs are; wire-provided GT elements
    are gated by batching.gt_membership_ok first)."""
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    F12 = make_fp12(F2)
    sqr = F12["csqr"] if cyc else F12["sqr"]
    B = f_ref.shape[-1]
    k = k_ref[:]
    n_win = (n_bits + wbits - 1) // wbits
    n_tab = 1 << wbits
    mask = np.uint32(n_tab - 1)

    rows = []
    for w in range(n_win - 1, -1, -1):          # MSB-first
        limb, s = divmod(wbits * w, params.LIMB_BITS)
        d = k[limb] >> np.uint32(s)
        if s + wbits > params.LIMB_BITS and limb + 1 < NL:
            # window straddles a limb boundary
            d = d | (k[limb + 1] << np.uint32(params.LIMB_BITS - s))
        rows.append(d & mask)
    dig_ref[:] = jnp.stack(rows)                # (n_win, B)

    base = _f12_load(f_ref)
    tab = [_f12_one_tiles(one_ref[:], B), base]
    for d in range(2, n_tab):
        tab.append(sqr(tab[d // 2]) if d % 2 == 0
                   else F12["mul"](tab[d - 1], base))

    def select(d):
        acc = tab[0]
        for v in range(1, n_tab):
            acc = _f12_select(d == v, tab[v], acc)
        return acc

    acc0 = select(dig_ref[0])

    def body(w, acc):
        for _ in range(wbits):
            acc = sqr(acc)
        d = dig_ref[pl.ds(w, 1), :][0]
        return F12["mul"](acc, select(d))

    acc = jax.lax.fori_loop(jnp.int32(1), jnp.int32(n_win), body, acc0)
    _f12_store(o_ref, acc)


def _f12_mulreduce8_kernel(m_ref, np_ref, g_ref, o_ref):
    """Product of 8 Fp12 values per lane: g_ref (8, 12, 16, B) -> (12, 16, B).
    Applied twice this reduces the 64 gathered window entries of a
    fixed-base GT exponentiation (gt_pow_fixed) — no squarings at all."""
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    F12 = make_fp12(F2)

    def load(w):
        return [(g_ref[w, 2 * k], g_ref[w, 2 * k + 1]) for k in range(6)]

    acc = load(0)
    for w in range(1, 8):
        acc = F12["mul"](acc, load(w))
    _f12_store(o_ref, acc)


def _f12_slotmul_kernel(m_ref, np_ref, c_ref, a_ref, o_ref,
                        *, conj_fp2: bool):
    """out[k] = (conj(a[k]) if conj_fp2 else a[k]) * c[k] — the shape of
    every Frobenius power on the flat tower (pairing._frob1/2/3) and of
    conj6 (constants (+-1)^k, conj_fp2=False). c_ref: (12, 16, LANES),
    constants pre-broadcast across lanes on the host (in-kernel constant
    broadcasts hit the unimplemented Mosaic sublane+lane path)."""
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    f = _f12_load(a_ref)
    out = []
    for k in range(6):
        c = (c_ref[2 * k], c_ref[2 * k + 1])
        x = F2["conj"](f[k]) if conj_fp2 else f[k]
        out.append(F2["mul"](x, c))
    _f12_store(o_ref, out)


_FROB_TILES = {}


def _frob_tiles(which) -> np.ndarray:
    """(12, 16, LANES) Montgomery Fp2 constants for frob1/2/3 or conj6,
    pre-broadcast across lanes."""
    if which in _FROB_TILES:
        return _FROB_TILES[which]
    from . import refimpl

    if which == "conj6":
        consts = [(1, 0) if k % 2 == 0 else (params.P - 1, 0)
                  for k in range(6)]
    else:
        e = {"frob1": 1, "frob2": 2, "frob3": 3}[which]
        g = refimpl.fp2_pow(params.XI, (params.P ** e - 1) // 6)
        consts, cur = [], (1, 0)
        for _k in range(6):
            consts.append(cur)
            cur = refimpl.fp2_mul(cur, g)
    rows = []
    for c in consts:
        for comp in c:
            rows.append(np.asarray(
                params.to_limbs(comp * params.R % params.P), dtype=np.uint32))
    _FROB_TILES[which] = np.broadcast_to(
        np.stack(rows)[:, :, None], (12, NL, LANES)).copy()
    return _FROB_TILES[which]


@functools.partial(jax.jit, static_argnames=("which", "interpret"))
def _f12_slotmul_flat(a, which: str, interpret: bool):
    N = a.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    c_in = jnp.asarray(_frob_tiles(which), dtype=jnp.uint32)
    io = _f12_io(n_tiles, Np, 1)
    io["in_specs"].insert(2, pl.BlockSpec((12, NL, LANES),
                                          lambda i: (0, 0, 0),
                                          memory_space=pltpu.VMEM))
    conj_fp2 = which in ("frob1", "frob3")
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_f12_slotmul_kernel, conj_fp2=conj_fp2),
            interpret=interpret, **io)(m_in, np_in, c_in, _to_tiles(a, Np))
    return _from_tiles(out, N)


def f12_slotmul_flat(a, which: str):
    """Frobenius^e / conj6 on (N, 6, 2, 16): which in
    {frob1, frob2, frob3, conj6}."""
    return _f12_slotmul_flat(a, which, INTERPRET)


def _f12_io(n_tiles, Np, n_inputs):
    specs = [
        pl.BlockSpec((NL, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
    ]
    specs += [pl.BlockSpec((12, NL, LANES), lambda i: (0, 0, i),
                           memory_space=pltpu.VMEM)] * n_inputs
    return dict(
        grid=(n_tiles,),
        in_specs=specs,
        out_specs=pl.BlockSpec((12, NL, LANES), lambda i: (0, 0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((12, NL, Np), jnp.uint32),
    )


def _to_tiles(f, Np):
    """(N, 6, 2, 16) -> (12, 16, Np)."""
    N = f.shape[0]
    return _pad_lanes(jnp.transpose(f.reshape(N, 12, NL), (1, 2, 0)), Np)


def _from_tiles(t, N):
    return jnp.transpose(t, (2, 0, 1))[:N].reshape(N, 6, 2, NL)


def _mnp():
    return (jnp.asarray(_M_FP[:, None], dtype=jnp.uint32),
            jnp.asarray([[_NPRIME_FP]], dtype=jnp.uint32))


@functools.partial(jax.jit, static_argnames="interpret")
def _f12_mul_flat(a, b, interpret: bool):
    N = a.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    with jax.enable_x64(False):
        out = pl.pallas_call(_f12_mul_kernel, interpret=interpret,
                             **_f12_io(n_tiles, Np, 2))(
            m_in, np_in, _to_tiles(a, Np), _to_tiles(b, Np))
    return _from_tiles(out, N)


def f12_mul_flat(a, b):
    """(N, 6, 2, 16) x (N, 6, 2, 16) -> (N, 6, 2, 16)."""
    return _f12_mul_flat(a, b, INTERPRET)


@functools.partial(jax.jit, static_argnames="interpret")
def _f12_inv_flat(a, interpret: bool):
    N = a.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    bits_in = jnp.asarray(_pm2_bits_tiles(), dtype=jnp.uint32)
    io = _f12_io(n_tiles, Np, 1)
    io["in_specs"].insert(2, pl.BlockSpec(
        (len(_PM2_BITS), LANES), lambda i: (0, 0),
        memory_space=pltpu.VMEM))
    with jax.enable_x64(False):
        out = pl.pallas_call(_f12_inv_kernel, interpret=interpret, **io)(
            m_in, np_in, bits_in, _to_tiles(a, Np))
    return _from_tiles(out, N)


def f12_inv_flat(a):
    return _f12_inv_flat(a, INTERPRET)


@functools.partial(jax.jit, static_argnames=("n_bits", "interpret"))
def _f12_pow_flat(f, k, n_bits: int, interpret: bool):
    N = f.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    one_in = jnp.asarray(np.asarray(
        params.to_limbs(params.R % params.P), dtype=np.uint32)[:, None], dtype=jnp.uint32)
    kt = _pad_lanes(jnp.transpose(k, (1, 0)), Np)
    io = _f12_io(n_tiles, Np, 1)
    # insert the one-column spec BEFORE the f12 input, append the exponent
    io["in_specs"].insert(2, pl.BlockSpec((NL, 1), lambda i: (0, 0),
                                          memory_space=pltpu.VMEM))
    io["in_specs"].append(pl.BlockSpec((NL, LANES), lambda i: (0, i),
                                       memory_space=pltpu.VMEM))
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_f12_pow_kernel, n_bits=n_bits),
            scratch_shapes=[pltpu.VMEM((n_bits, LANES), jnp.uint32)],
            interpret=interpret, **io)(
            m_in, np_in, one_in, _to_tiles(f, Np), kt)
    return _from_tiles(out, N)


def f12_pow_flat(f, k, n_bits: int = 256):
    """f^k batched: f (N, 6, 2, 16), k (N, 16) plain limbs (LSB-first bits;
    n_bits < 256 truncates for exponents known to be short, e.g. |u| = 63)."""
    return _f12_pow_flat(f, k, n_bits, INTERPRET)


@functools.partial(jax.jit,
                   static_argnames=("n_bits", "wbits", "cyc", "interpret"))
def _f12_wpow_flat(f, k, n_bits: int, wbits: int, cyc: bool,
                   interpret: bool):
    N = f.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    n_win = (n_bits + wbits - 1) // wbits
    m_in, np_in = _mnp()
    one_in = jnp.asarray(np.asarray(
        params.to_limbs(params.R % params.P), dtype=np.uint32)[:, None], dtype=jnp.uint32)
    kt = _pad_lanes(jnp.transpose(k, (1, 0)), Np)
    io = _f12_io(n_tiles, Np, 1)
    io["in_specs"].insert(2, pl.BlockSpec((NL, 1), lambda i: (0, 0),
                                          memory_space=pltpu.VMEM))
    io["in_specs"].append(pl.BlockSpec((NL, LANES), lambda i: (0, i),
                                       memory_space=pltpu.VMEM))
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_f12_wpow_kernel, n_bits=n_bits, wbits=wbits,
                              cyc=cyc),
            scratch_shapes=[pltpu.VMEM((n_win, LANES), jnp.uint32)],
            interpret=interpret, **io)(
            m_in, np_in, one_in, _to_tiles(f, Np), kt)
    return _from_tiles(out, N)


def f12_wpow_flat(f, k, n_bits: int = 256, wbits: int = 3,
                  cyc: bool = False):
    """Windowed f^k batched: f (N, 6, 2, 16), k (N, 16) plain limbs.
    cyc=True uses cyclotomic squarings (requires f ∈ GΦ12 — see kernel)."""
    return _f12_wpow_flat(f, k, n_bits, wbits, cyc, INTERPRET)


def _f12_csqr_kernel(m_ref, np_ref, a_ref, o_ref):
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    F12 = make_fp12(F2)
    _f12_store(o_ref, F12["csqr"](_f12_load(a_ref)))


@functools.partial(jax.jit, static_argnames="interpret")
def _f12_csqr_flat(a, interpret: bool):
    N = a.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    with jax.enable_x64(False):
        out = pl.pallas_call(_f12_csqr_kernel, interpret=interpret,
                             **_f12_io(n_tiles, Np, 1))(
            m_in, np_in, _to_tiles(a, Np))
    return _from_tiles(out, N)


def f12_csqr_flat(a):
    """Cyclotomic squaring, (N, 6, 2, 16) -> (N, 6, 2, 16). Input MUST be
    in GΦ12 (pairing outputs after final exp are)."""
    return _f12_csqr_flat(a, INTERPRET)


@functools.partial(jax.jit, static_argnames="interpret")
def _f12_mulreduce8_flat(g, interpret: bool):
    N = g.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    gt = _pad_lanes(jnp.transpose(g.reshape(N, 8, 12, NL), (1, 2, 3, 0)), Np)
    io = _f12_io(n_tiles, Np, 0)
    io["in_specs"].append(pl.BlockSpec((8, 12, NL, LANES),
                                       lambda i: (0, 0, 0, i),
                                       memory_space=pltpu.VMEM))
    with jax.enable_x64(False):
        out = pl.pallas_call(_f12_mulreduce8_kernel, interpret=interpret,
                             **io)(m_in, np_in, gt)
    return _from_tiles(out, N)


def f12_mulreduce8_flat(g):
    """(N, 8, 6, 2, 16) -> (N, 6, 2, 16): per-row product of 8 values."""
    return _f12_mulreduce8_flat(g, INTERPRET)


def window_digits(k, n_win: int = 64):
    """(..., 16) plain limbs -> (..., n_win) 4-bit window values, LSB-first."""
    outs = []
    for w in range(n_win):
        limb, s = divmod(4 * w, params.LIMB_BITS)
        outs.append((k[..., limb] >> np.uint32(s)) & np.uint32(0xF))
    return jnp.stack(outs, axis=-1)


def gt_pow_fixed(table, k):
    """base^k for a FIXED base via its precomputed window table.

    table: (64, 16, 6, 2, 16) with table[w][j] = base^(j * 16^w); k: (N, 16)
    plain limbs. Gathers one entry per window at the XLA level, then reduces
    the 64 entries with two passes of the 8-way product kernel — 63 Fp12
    muls and zero squarings per element (vs 256 sqr + 256 mul for the
    generic ladder). Used for gtB^t in proof creation and gtB^Zv in
    verification (range_proof.py), where the base e(B, B2) never changes.
    """
    N = k.shape[0]
    digs = window_digits(k)                     # (N, 64)
    g = table[jnp.arange(64)[None, :], digs]    # (N, 64, 6, 2, 16)
    r1 = f12_mulreduce8_flat(g.reshape(N * 8, 8, 6, 2, NL))
    return f12_mulreduce8_flat(r1.reshape(N, 8, 6, 2, NL))


def gt_pow_fixed_multi(tables, base_idx, k):
    """bases[base_idx]^k where every element selects one of a SMALL set of
    fixed bases, each with a precomputed window table.

    tables: (NB, 64, 16, 6, 2, 16) — per-base 4-bit window tables
    (tables[b][w][j] = base_b^(j * 16^w)); base_idx: (N,) int32;
    k: (N, 16) plain limbs. Same 63-mul/zero-squaring reduction as
    gt_pow_fixed, reusing the mulreduce8 kernel. This is the creation-side
    digit pow gtA[i][phi]^(-s v): only ns*u distinct bases exist, so the
    one-time table build (host oracle, cached per signature set) amortizes
    over every proof — ~2.7x fewer Montgomery muls than even the
    cyclotomic windowed pow chain."""
    N = k.shape[0]
    digs = window_digits(k)                     # (N, 64)
    g = tables[base_idx[:, None], jnp.arange(64)[None, :], digs]
    r1 = f12_mulreduce8_flat(g.reshape(N * 8, 8, 6, 2, NL))
    return f12_mulreduce8_flat(r1.reshape(N, 8, 6, 2, NL))


# ---------------------------------------------------------------------------
# Field inversion kernels (Fermat chains; replace the sequential
# Montgomery-trick batch inversion, which scans over the BATCH axis and
# crawls on TPU) + G2 windowed scalar-mult ladder
# ---------------------------------------------------------------------------

def _fp_inv_kernel(m_ref, np_ref, bits_ref, x_ref, o_ref):
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    o_ref[:] = _fermat_inv_rolled(x_ref[:], F2["fp_mul"], bits_ref)


def _inv_bits_spec():
    return pl.BlockSpec((len(_PM2_BITS), LANES), lambda i: (0, 0),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames="interpret")
def _fp_inv_flat(x, interpret: bool):
    N = x.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    bits_in = jnp.asarray(_pm2_bits_tiles(), dtype=jnp.uint32)
    xt = _pad_lanes(jnp.transpose(x, (1, 0)), Np)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _fp_inv_kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((NL, 1), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                _inv_bits_spec(),
                pl.BlockSpec((NL, LANES), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((NL, LANES), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((NL, Np), jnp.uint32),
            interpret=interpret,
        )(m_in, np_in, bits_in, xt)
    return jnp.transpose(out, (1, 0))[:N]


def fp_inv_flat(x):
    """x^(p-2) batched: (N, 16) Montgomery -> (N, 16) Montgomery."""
    return _fp_inv_flat(x, INTERPRET)


def _f2_inv_kernel(m_ref, np_ref, bits_ref, a_ref, o_ref):
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    a = (a_ref[0], a_ref[1])
    # norm = a0^2 + a1^2; inv via Fermat; out = (a0*ni, -a1*ni)
    n = F2["fp_add"](F2["fp_mul"](a[0], a[0]), F2["fp_mul"](a[1], a[1]))
    acc = _fermat_inv_rolled(n, F2["fp_mul"], bits_ref)
    z = jnp.zeros_like(a[1])
    o_ref[0] = F2["fp_mul"](a[0], acc)
    o_ref[1] = F2["fp_mul"](F2["fp_sub"](z, a[1]), acc)


@functools.partial(jax.jit, static_argnames="interpret")
def _f2_inv_flat(a, interpret: bool):
    N = a.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    m_in, np_in = _mnp()
    bits_in = jnp.asarray(_pm2_bits_tiles(), dtype=jnp.uint32)
    at = _pad_lanes(jnp.transpose(a, (1, 2, 0)), Np)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _f2_inv_kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((NL, 1), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                _inv_bits_spec(),
                pl.BlockSpec((2, NL, LANES), lambda i: (0, 0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((2, NL, LANES), lambda i: (0, 0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((2, NL, Np), jnp.uint32),
            interpret=interpret,
        )(m_in, np_in, bits_in, at)
    return jnp.transpose(out, (2, 0, 1))[:N]


def f2_inv_flat(a):
    """Fp2 inverse batched: (N, 2, 16) Montgomery -> (N, 2, 16)."""
    return _f2_inv_flat(a, INTERPRET)


def _f2_is_zero(a):
    from .pallas_ops import fis_zero

    return fis_zero(a[0]) & fis_zero(a[1])


def make_g2_group(F2):
    """Complete Jacobian group law on the twist (Fp2 tiles); mirrors
    pallas_ops.make_group with Fp2 arithmetic and crypto/g2.py formulas."""

    def sel(cond, p, q):
        c = cond[None, :]
        return tuple((jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1]))
                     for a, b in zip(p, q))

    def inf_like(p):
        one = jnp.ones((1,) + p[0][0].shape[1:], jnp.uint32)
        zeros = jnp.zeros((NL - 1,) + p[0][0].shape[1:], jnp.uint32)
        X0 = jnp.concatenate([one, zeros], axis=0)
        zt = jnp.zeros_like(p[0][0])
        return ((X0, zt), (X0, zt), (zt, zt))

    def pdouble(p):
        X, Y, Z = p
        A = F2["sqr"](X)
        Bv = F2["sqr"](Y)
        Cv = F2["sqr"](Bv)
        t = F2["sub"](F2["sqr"](F2["add"](X, Bv)), F2["add"](A, Cv))
        D = F2["add"](t, t)
        E = F2["add"](F2["add"](A, A), A)
        Fv = F2["sqr"](E)
        X3 = F2["sub"](Fv, F2["add"](D, D))
        C2 = F2["add"](Cv, Cv)
        C8 = F2["add"](F2["add"](C2, C2), F2["add"](C2, C2))
        Y3 = F2["sub"](F2["mul"](E, F2["sub"](D, X3)), C8)
        YZ = F2["mul"](Y, Z)
        Z3 = F2["add"](YZ, YZ)
        return (X3, Y3, Z3)

    def padd(p, q):
        X1, Y1, Z1 = p
        X2, Y2, Z2 = q
        Z1Z1 = F2["sqr"](Z1)
        Z2Z2 = F2["sqr"](Z2)
        U1 = F2["mul"](X1, Z2Z2)
        U2 = F2["mul"](X2, Z1Z1)
        S1 = F2["mul"](Y1, F2["mul"](Z2, Z2Z2))
        S2 = F2["mul"](Y2, F2["mul"](Z1, Z1Z1))
        H = F2["sub"](U2, U1)
        HH = F2["add"](H, H)
        I = F2["sqr"](HH)
        J = F2["mul"](H, I)
        r = F2["sub"](S2, S1)
        r = F2["add"](r, r)
        V = F2["mul"](U1, I)
        X3 = F2["sub"](F2["sub"](F2["sqr"](r), J), F2["add"](V, V))
        SJ = F2["mul"](S1, J)
        Y3 = F2["sub"](F2["mul"](r, F2["sub"](V, X3)), F2["add"](SJ, SJ))
        t1 = F2["add"](Z1, Z2)
        ZZ = F2["sub"](F2["sub"](F2["sqr"](t1), Z1Z1), Z2Z2)
        Z3 = F2["mul"](ZZ, H)
        res = (X3, Y3, Z3)

        p_inf = _f2_is_zero(Z1)
        q_inf = _f2_is_zero(Z2)
        h0 = _f2_is_zero(H)
        r0 = _f2_is_zero(r)
        res = sel(h0 & r0 & ~p_inf & ~q_inf, pdouble(p), res)
        res = sel(h0 & ~r0 & ~p_inf & ~q_inf, inf_like(p), res)
        res = sel(q_inf, p, res)
        res = sel(p_inf, q, res)
        return res

    return pdouble, padd, inf_like


def _g2_scalar_mul_kernel(m_ref, np_ref, p_ref, k_ref, o_ref, dig_ref):
    """Windowed (4-bit) ladder on the twist — the Fp2 analogue of
    pallas_ops._scalar_mul_kernel. p_ref: (6, 16, B) = (X0,X1,Y0,Y1,Z0,Z1)
    Jacobian Montgomery; k_ref: (16, B) plain scalars."""
    F2 = make_fp2(m_ref[:], np_ref[0, 0])
    pdouble, padd, inf_like = make_g2_group(F2)

    P = ((p_ref[0], p_ref[1]), (p_ref[2], p_ref[3]), (p_ref[4], p_ref[5]))
    k = k_ref[:]

    tab = [inf_like(P), P]
    for d in range(2, 16):
        tab.append(pdouble(tab[d // 2]) if d % 2 == 0
                   else padd(tab[d - 1], P))
    # (16, 6, 16, B) stacked coordinate components for per-lane select
    comp = [jnp.stack([t[c][i] for t in tab])
            for c in range(3) for i in range(2)]

    rows = []
    for w in range(63, -1, -1):
        limb, s = divmod(w, 4)
        rows.append((k[limb] >> np.uint32(4 * s)) & np.uint32(0xF))
    dig_ref[:] = jnp.stack(rows)              # (64, B) MSB first

    def select(d):
        accs = [c[0] for c in comp]
        for v in range(1, 16):
            mask = (d == v)[None, :]
            accs = [jnp.where(mask, c[v], a) for c, a in zip(comp, accs)]
        return ((accs[0], accs[1]), (accs[2], accs[3]), (accs[4], accs[5]))

    acc0 = select(dig_ref[0])

    def body(w, acc):
        acc = pdouble(pdouble(pdouble(pdouble(acc))))
        d = dig_ref[pl.ds(w, 1), :][0]
        return padd(acc, select(d))

    acc = jax.lax.fori_loop(jnp.int32(1), jnp.int32(64), body, acc0)
    o_ref[0], o_ref[1] = acc[0]
    o_ref[2], o_ref[3] = acc[1]
    o_ref[4], o_ref[5] = acc[2]


@functools.partial(jax.jit, static_argnames="interpret")
def _g2_scalar_mul_flat(p, k, interpret: bool):
    N = p.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    pt = _pad_lanes(jnp.transpose(p.reshape(N, 6, NL), (1, 2, 0)), Np)
    kt = _pad_lanes(jnp.transpose(k, (1, 0)), Np)
    m_in, np_in = _mnp()
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _g2_scalar_mul_kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((NL, 1), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((6, NL, LANES), lambda i: (0, 0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((NL, LANES), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((6, NL, LANES), lambda i: (0, 0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((6, NL, Np), jnp.uint32),
            scratch_shapes=[pltpu.VMEM((64, LANES), jnp.uint32)],
            interpret=interpret,
        )(m_in, np_in, pt, kt)
    return jnp.transpose(out, (2, 0, 1))[:N].reshape(N, 3, 2, NL)


def g2_scalar_mul_flat(p, k):
    """k*Q batched: p (N, 3, 2, 16) Jacobian Montgomery, k (N, 16) plain
    scalars -> (N, 3, 2, 16)."""
    return _g2_scalar_mul_flat(p, k, INTERPRET)


# ---------------------------------------------------------------------------
# Full pairing: miller kernel + final exp (kernels + light jnp glue)
# ---------------------------------------------------------------------------

_U_LIMBS = None


def _u_limbs(N):
    global _U_LIMBS
    if _U_LIMBS is None:
        _U_LIMBS = np.asarray(params.to_limbs(params.U), dtype=np.uint32)
    return jnp.broadcast_to(jnp.asarray(_U_LIMBS, dtype=jnp.uint32), (N, NL))


def final_exp_flat(f):
    """Reduced pairing final exponentiation, batched (N, 6, 2, 16).

    Same structure as pairing.final_exp: easy part, then the DSD hard part
    with 3 exponentiations by u (63-bit pow kernel) + Frobenius maps (jnp —
    conjugation and 6 constant Fp2 muls are cheap) + the Olivos chain via
    the mul kernel. After the easy part every operand lives in GΦ12(p)
    (f^((p^6-1)(p^2+1)) kills the rest of the group order), so the u-pows
    and all explicit squarings use cyclotomic squarings — 2x per squaring.
    """
    N = f.shape[0]

    def frob(g, which: int):
        return f12_slotmul_flat(g, f"frob{which}")

    def conj(g):
        return f12_slotmul_flat(g, "conj6")

    mul = f12_mul_flat
    u = _u_limbs(N)

    f1 = mul(conj(f), f12_inv_flat(f))
    f2 = mul(frob(f1, 2), f1)

    fx = f12_wpow_flat(f2, u, n_bits=params.U.bit_length(), cyc=True)
    fx2 = f12_wpow_flat(fx, u, n_bits=params.U.bit_length(), cyc=True)
    fx3 = f12_wpow_flat(fx2, u, n_bits=params.U.bit_length(), cyc=True)

    y0 = mul(mul(frob(f2, 1), frob(f2, 2)), frob(f2, 3))
    y1 = conj(f2)
    y2 = frob(fx2, 2)
    y3 = conj(frob(fx, 1))
    y4 = conj(mul(fx, frob(fx2, 1)))
    y5 = conj(fx2)
    y6 = conj(mul(fx3, frob(fx3, 1)))

    sqr = f12_csqr_flat
    t0 = mul(mul(sqr(y6), y4), y5)
    t1 = mul(mul(y3, y5), t0)
    t0 = mul(t0, y2)
    t1 = mul(sqr(t1), t0)
    t1 = sqr(t1)
    t0b = mul(t1, y1)
    t1 = mul(t1, y0)
    t0b = sqr(t0b)
    return mul(t0b, t1)


def pair_flat(px, py, qx, qy):
    """Full reduced optimal ate pairing, batched flat inputs:
    px, py (N, 16); qx, qy (N, 2, 16) -> (N, 6, 2, 16)."""
    return final_exp_flat(miller_flat(px, py, qx, qy))


__all__ = ["miller_flat", "f12_mul_flat", "f12_inv_flat", "f12_pow_flat",
           "f12_wpow_flat", "f12_csqr_flat", "f12_mulreduce8_flat",
           "f12_slotmul_flat", "final_exp_flat", "pair_flat",
           "fp_inv_flat", "f2_inv_flat", "g2_scalar_mul_flat",
           "gt_pow_fixed", "window_digits"]
