"""Pallas TPU kernels for the hot crypto ops (SURVEY.md §7 stage 1).

The jnp field/curve layers put the 16 scalar limbs on the MINOR axis, so a
(B, 16) uint32 op wastes 7/8 of every 128-wide VPU lane register and every
scan step is a separate XLA op with HBM round-trips. These kernels flip the
layout — limbs on sublanes, batch on lanes — and run the entire windowed
scalar-multiplication ladder in one kernel: table build, digit scan, field
arithmetic all in VMEM/registers. This is the TPU-native replacement for the
per-point goroutine fan-out around kyber Point.Mul in the reference (unlynx
StartParallelize at lib/range/range_proof.go:75 and 30+ sites).

Field elements inside a kernel are (16, B) uint32 traced values (16-bit
limbs, little-endian, Montgomery form for Fp); points are (X, Y, Z) tuples of
those (Jacobian, Z == 0 at infinity) — the same representation as
crypto/field.py / crypto/curve.py, transposed.

That "sublane" layout (limbs on sublanes, B = 128 lanes: a field element is
two vregs) still describes `_point_add_kernel`, `_point_reduce_kernel` and
the kernels of crypto/pallas_pairing.py. The two ladders, `_fixed_base_kernel`
(PR 33) and `_scalar_mul_kernel` (PR 35), keep a field element as "limb
tiles" instead: 16 tiles of (8, 128) uint32, limb l of TILE_LANES = 1 024
lanes in one vreg, so every step of a carry, borrow or reduction chain is one
whole-vreg operation and no row is ever moved (the sublane product spends
half its operations on sublane rotates and selects). Each layout is a `Field`
bundle; `make_group` writes the group law once over either.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import params

NL = params.NUM_LIMBS            # 16
LB = params.LIMB_BITS            # 16
MASK = np.uint32(params.LIMB_MASK)  # numpy literal: safe inside kernels

_M_FP = np.asarray(params.to_limbs(params.P), dtype=np.uint32)
_NPRIME_FP = np.uint32(params.NPRIME)
_N_ORDER = np.asarray(params.to_limbs(params.N), dtype=np.uint32)
_ONE_MONT = np.asarray(params.to_limbs(params.R % params.P), dtype=np.uint32)

LANES = 128                      # batch tile width, sublane layout
TILE_LANES = 8 * LANES           # batch tile width of the limb-tile layout
                                 # (the two ladders'): one vreg a limb

# DRYNX_PALLAS_INTERPRET=1 runs the kernels through the Pallas interpreter
# (any backend) — used by the CPU test suite to cover the kernel code paths.
# The flag is read at CALL time by the thin non-jitted public wrappers and
# passed into the jitted entry points as a STATIC argument, so flipping it
# (tests monkeypatch the module global) keys a fresh trace instead of
# leaking a stale interpret-mode executable out of the jit cache.
INTERPRET = os.environ.get("DRYNX_PALLAS_INTERPRET", "0") == "1"


class Field(NamedTuple):
    """One layout's field functions, what `make_group` builds the group
    law from. `select(cond, p, q)` is the per-lane POINT select."""
    mul: Callable
    add: Callable
    sub: Callable
    is_zero: Callable
    select: Callable
    one_like: Callable
    inf_like: Callable


# ---------------------------------------------------------------------------
# Field arithmetic on (16, B) tiles (trace-time unrolled; ~16-step chains)
# ---------------------------------------------------------------------------

def _sub_limbs(a, b):
    """a - b with borrow chain. Returns ((16, B), borrow (B,))."""
    outs = []
    borrow = jnp.zeros(a.shape[1:], jnp.uint32)
    for k in range(NL):
        v = a[k] - b[k] - borrow
        outs.append(v & MASK)
        borrow = (v >> LB) & np.uint32(1)
    return jnp.stack(outs), borrow


def _carry16(rows, carry0=None):
    """Propagate carries down 16 rows (values < 2^31). -> ((16,B), carry)."""
    outs = []
    c = jnp.zeros(rows.shape[1:], jnp.uint32) if carry0 is None else carry0
    for k in range(NL):
        v = rows[k] + c
        outs.append(v & MASK)
        c = v >> LB
    return jnp.stack(outs), c


def fadd(a, b, m):
    """(a + b) mod m on (16, B) tiles, inputs normalized."""
    s, carry = _carry16(a + b)
    diff, borrow = _sub_limbs(s, jnp.broadcast_to(m, s.shape))
    use_diff = (borrow == 0) | (carry > 0)
    return jnp.where(use_diff[None, :], diff, s)


def fsub(a, b, m):
    diff, borrow = _sub_limbs(a, b)
    plus_m, _ = _carry16(diff + m)
    return jnp.where((borrow == 1)[None, :], plus_m, diff)


def fis_zero(a):
    """(B,) bool: all 16 limbs zero. Unrolled OR-tree — Mosaic lowers
    boolean sublane reductions through an unsupported float path."""
    orv = a[0]
    for k in range(1, NL):
        orv = orv | a[k]
    return orv == 0


def _padded_add(cols, block, off):
    """cols (33, B) + block (R, B) placed at row offset `off` (static).

    Mosaic has no scatter; static-offset placement is a concat of zero rows.
    """
    R = block.shape[0]
    parts = []
    if off:
        parts.append(jnp.zeros((off,) + block.shape[1:], jnp.uint32))
    parts.append(block)
    tail = cols.shape[0] - off - R
    if tail:
        parts.append(jnp.zeros((tail,) + block.shape[1:], jnp.uint32))
    return cols + jnp.concatenate(parts, axis=0)


def mont_mul(a, b, m, nprime):
    """Montgomery product on (16, B) tiles (same math as field.mont_mul's
    unrolled path: schoolbook columns + 16 interleaved reduction steps)."""
    B = a.shape[1]
    zrow = jnp.zeros((1, B), jnp.uint32)
    cols = jnp.zeros((2 * NL + 1, B), jnp.uint32)
    for j in range(NL):
        p = a * b[j][None, :]        # (16, B), full 32-bit products
        # lo lands in cols[j:j+16], hi in cols[j+1:j+17] -> one (17,B) block
        add17 = (jnp.concatenate([p & MASK, zrow], axis=0)
                 + jnp.concatenate([zrow, p >> LB], axis=0))
        cols = _padded_add(cols, add17, j)
    carry = jnp.zeros((B,), jnp.uint32)
    for i in range(NL):
        v = cols[i] + carry
        mfac = ((v & MASK) * nprime) & MASK
        mp = m * mfac[None, :]       # (16, B)
        mlo = mp & MASK
        carry = (v + mlo[0]) >> LB
        # mlo[1:] lands in cols[i+1:i+16], hi in cols[i+1:i+17]
        add16 = (jnp.concatenate([mlo[1:], zrow], axis=0) + (mp >> LB))
        cols = _padded_add(cols, add16, i + 1)
    res, c = _carry16(cols[NL:2 * NL], carry0=carry)
    top = cols[2 * NL] + c
    diff, borrow = _sub_limbs(res, jnp.broadcast_to(m, res.shape))
    use_diff = (borrow == 0) | (top > 0)
    return jnp.where(use_diff[None, :], diff, res)


def _pt_select(cond, p, q):
    """Per-lane select: cond (B,) bool -> p where true else q."""
    c = cond[None, :]
    return tuple(jnp.where(c, a, b) for a, b in zip(p, q))


def _inf_like(p):
    """Infinity point tiles shaped like p, as `curve.infinity` writes it:
    X = Y = the Montgomery one, Z = 0. Any X, Y mean infinity where
    Z == 0; the jnp layer's limbs keep `padd` byte-identical to
    `curve.add` in every case (tests/test_dro.py)."""
    one = _one_like(p[0])
    return (one, one, jnp.zeros_like(p[2]))


def _one_like(a):
    """The Montgomery one (R mod p) as tiles shaped like a."""
    return jnp.stack([jnp.full(a.shape[1:], l, jnp.uint32)
                      for l in _ONE_MONT])


def canonical_scalar(k, n):
    """k mod n for any 256-bit k on (16, B) tiles, n (16, 1) the group
    order's limbs: 2n > 2^256, so one conditional subtraction suffices."""
    diff, borrow = _sub_limbs(k, jnp.broadcast_to(n, k.shape))
    return jnp.where((borrow == 0)[None, :], diff, k)


def sublane_field(m_const, nprime) -> Field:
    """The (16, B) layout's bundle, the modulus constants bound once."""
    return Field(mul=lambda a, b: mont_mul(a, b, m_const, nprime),
                 add=lambda a, b: fadd(a, b, m_const),
                 sub=lambda a, b: fsub(a, b, m_const),
                 is_zero=fis_zero, select=_pt_select,
                 one_like=_one_like, inf_like=_inf_like)


# ---------------------------------------------------------------------------
# Field arithmetic on limb tiles: a field element is 16 arrays of one shape
# (in a kernel (8, 128): limb l of TILE_LANES lanes in one vreg), given as a
# list or as an array with the limbs on its MAJOR axis. Every step of a chain
# is a whole-vreg operation; the moduli's limbs are scalar constants.
# ---------------------------------------------------------------------------

def _tile_sub(a, b):
    """a - b with borrow chain, b's limbs arrays or scalars.
    -> (16 limbs, borrow)."""
    outs = []
    borrow = None
    for k in range(NL):
        v = a[k] - b[k]
        if borrow is not None:
            v = v - borrow
        outs.append(v & MASK)
        borrow = v >> np.uint32(31)
    return outs, borrow


def _tile_carry(limbs, carry=None):
    """Propagate carries up 16 limbs (values < 2^31). -> (limbs, carry)."""
    outs = []
    for v in limbs:
        if carry is not None:
            v = v + carry
        outs.append(v & MASK)
        carry = v >> LB
    return outs, carry


def _tile_where(cond, a, b):
    return [jnp.where(cond, x, y) for x, y in zip(a, b)]


def tile_fadd(a, b):
    """(a + b) mod p, inputs normalized."""
    s, carry = _tile_carry([a[k] + b[k] for k in range(NL)])
    diff, borrow = _tile_sub(s, _M_FP)
    return _tile_where((borrow == 0) | (carry > 0), diff, s)


def tile_fsub(a, b):
    """(a - b) mod p, inputs normalized."""
    diff, borrow = _tile_sub(a, b)
    plus_m, _ = _tile_carry([diff[k] + _M_FP[k] for k in range(NL)])
    return _tile_where(borrow == 1, plus_m, diff)


def tile_fis_zero(a):
    """Bool of the limbs' shape: all 16 limbs zero."""
    orv = a[0]
    for k in range(1, NL):
        orv = orv | a[k]
    return orv == 0


def tile_mont_mul(a, b):
    """Montgomery product a * b * R^-1 mod p: product scanning with lazy
    carries. A partial product's halves go to their columns unpropagated
    (a column sums at most 64 values under 2^16 and a carry: no overflow),
    the 16 reduction steps add mfac * p the same way, then one carry pass
    and one conditional subtraction."""
    cols = [None] * (2 * NL)

    def acc(k, v):
        cols[k] = v if cols[k] is None else cols[k] + v

    for i in range(NL):
        for j in range(NL):
            p = a[i] * b[j]
            acc(i + j, p & MASK)
            acc(i + j + 1, p >> LB)
    carry = None
    for i in range(NL):
        v = cols[i] if carry is None else cols[i] + carry
        mfac = ((v & MASK) * _NPRIME_FP) & MASK
        mp = mfac * _M_FP[0]
        carry = (v + (mp & MASK)) >> LB
        acc(i + 1, mp >> LB)
        for j in range(1, NL):
            mp = mfac * _M_FP[j]
            acc(i + j, mp & MASK)
            acc(i + j + 1, mp >> LB)
    res, top = _tile_carry(cols[NL:], carry)
    diff, borrow = _tile_sub(res, _M_FP)
    return _tile_where((borrow == 0) | (top > 0), diff, res)


def _tile_pt_select(cond, p, q):
    """Per-lane select: cond of the limbs' shape -> p where true else q."""
    return tuple(_tile_where(cond, a, b) for a, b in zip(p, q))


def _tile_one_like(a):
    """The Montgomery one (R mod p) as limb tiles shaped like a."""
    return [jnp.full(a[0].shape, l, jnp.uint32) for l in _ONE_MONT]


def _tile_inf_like(p):
    """Infinity as `_inf_like` writes it: X = Y = the Montgomery one."""
    one = _tile_one_like(p[0])
    return (one, one, [jnp.zeros_like(z) for z in p[2]])


def tile_canonical_scalar(k):
    """k mod n for any 256-bit k on limb tiles (see `canonical_scalar`)."""
    diff, borrow = _tile_sub(k, _N_ORDER)
    return _tile_where(borrow == 0, diff, k)


TILE_FIELD = Field(mul=tile_mont_mul, add=tile_fadd, sub=tile_fsub,
                   is_zero=tile_fis_zero, select=_tile_pt_select,
                   one_like=_tile_one_like, inf_like=_tile_inf_like)


# ---------------------------------------------------------------------------
# G1 group law on (X, Y, Z) tuples of field elements (mirrors crypto/curve.py),
# written once over a layout's `Field`
# ---------------------------------------------------------------------------

class Group(NamedTuple):
    """`make_group`'s G1 group law: the doubling, the complete addition,
    the mixed (Jacobian + affine) addition and the Jacobian addition of
    operands known to be unequal."""
    pdouble: Callable
    padd: Callable
    pmadd: Callable
    paddu: Callable


def make_group(field: Field) -> Group:
    """The G1 group law over one layout's field functions."""
    mul, add_, sub_ = field.mul, field.add, field.sub
    is_zero, select = field.is_zero, field.select

    def pdouble(p):
        X, Y, Z = p
        A = mul(X, X)
        Bv = mul(Y, Y)
        Cv = mul(Bv, Bv)
        t0 = add_(X, Bv)
        t = sub_(mul(t0, t0), add_(A, Cv))
        D = add_(t, t)
        E = add_(add_(A, A), A)
        Fv = mul(E, E)
        X3 = sub_(Fv, add_(D, D))
        C2 = add_(Cv, Cv)
        C4 = add_(C2, C2)
        C8 = add_(C4, C4)
        Y3 = sub_(mul(E, sub_(D, X3)), C8)
        YZ = mul(Y, Z)
        Z3 = add_(YZ, YZ)
        return (X3, Y3, Z3)

    def _add_distinct(p, q):
        """Jacobian + Jacobian where neither is infinity and q != p; for
        q == -p, H == 0 gives Z3 == 0 by itself. 16 products. Also hands
        back what `padd` reads to tell its cases apart."""
        X1, Y1, Z1 = p
        X2, Y2, Z2 = q
        Z1Z1 = mul(Z1, Z1)
        Z2Z2 = mul(Z2, Z2)
        U1 = mul(X1, Z2Z2)
        U2 = mul(X2, Z1Z1)
        S1 = mul(Y1, mul(Z2, Z2Z2))
        S2 = mul(Y2, mul(Z1, Z1Z1))
        H = sub_(U2, U1)
        HH = add_(H, H)
        I = mul(HH, HH)
        J = mul(H, I)
        r = sub_(S2, S1)
        r = add_(r, r)
        V = mul(U1, I)
        X3 = sub_(sub_(mul(r, r), J), add_(V, V))
        SJ = mul(S1, J)
        Y3 = sub_(mul(r, sub_(V, X3)), add_(SJ, SJ))
        t1 = add_(Z1, Z2)
        ZZ = sub_(sub_(mul(t1, t1), Z1Z1), Z2Z2)
        Z3 = mul(ZZ, H)
        return (X3, Y3, Z3), is_zero(Z1), is_zero(Z2), H, r

    def padd(p, q):
        res, p_inf, q_inf, H, r = _add_distinct(p, q)
        h0 = is_zero(H)
        r0 = is_zero(r)
        res = select(h0 & r0 & ~p_inf & ~q_inf, pdouble(p), res)
        res = select(h0 & ~r0 & ~p_inf & ~q_inf, field.inf_like(p), res)
        res = select(q_inf, p, res)
        res = select(p_inf, q, res)
        return res

    def paddu(p, q):
        """p + q, both Jacobian, for operands the caller knows to be
        UNEQUAL: 16 products, the 7 of the doubling that the complete
        `padd` computes in every lane and then discards left out.

        Complete for infinity on either side and for q == -p (Z3 == 0 by
        itself; X3, Y3 are then not `inf_like`'s). NOT for q == p (it
        would give Z3 == 0 too): a variable-base ladder over descending
        windows never meets it for a scalar below the group order
        (`_scalar_mul_kernel`)."""
        res, p_inf, q_inf, _, _ = _add_distinct(p, q)
        return select(p_inf, q, select(q_inf, p, res))

    def pmadd(p, x2, y2, q_inf):
        """p + q for an AFFINE addend q = (x2, y2), q_inf (B,) bool where q
        is the point at infinity (x2, y2 then arbitrary). 11 products
        (Hankerson-Menezes-Vanstone mixed addition: Z2 == 1 takes Z2Z2,
        U1, S1 and the (Z1+Z2)^2 of `padd` away), no doubling.

        Complete for infinity on either side and for q == -p (H == 0 gives
        Z3 == 0 by itself). NOT for q == p: the caller must know that the
        two finite operands differ, as a fixed-base ladder over ascending
        windows does for every scalar below the group order."""
        X1, Y1, Z1 = p
        Z1Z1 = mul(Z1, Z1)
        H = sub_(mul(x2, Z1Z1), X1)
        r = sub_(mul(y2, mul(Z1, Z1Z1)), Y1)
        Z3 = mul(Z1, H)
        HH = mul(H, H)
        HHH = mul(H, HH)
        V = mul(X1, HH)
        X3 = sub_(sub_(mul(r, r), HHH), add_(V, V))
        Y3 = sub_(mul(r, sub_(V, X3)), mul(Y1, HHH))
        res = select(is_zero(Z1), (x2, y2, field.one_like(Z1)),
                     (X3, Y3, Z3))
        return select(q_inf, p, res)

    return Group(pdouble, padd, pmadd, paddu)


# ---------------------------------------------------------------------------
# Windowed scalar-mult kernel: whole ladder in one pallas_call
# ---------------------------------------------------------------------------

def _scalar_mul_kernel(p_ref, k_ref, o_ref, tab_ref, dig_ref):
    """On limb tiles (TILE_FIELD): p_ref and o_ref (3, 16, 8, 128), k_ref
    (16, 8, 128), tab_ref (15, 3, 16, 8, 128): the table d * P, d in 1..15,
    at d - 1 (d = 0 is infinity, a constant), dig_ref (W, 8, 128): the 4-bit
    digits, MSB first. W < 64 serves scalars known to be < 16^W (62-bit RLC
    weights: 16 windows). The table build and the four doublings of a
    window are loops, so that the trace holds `pdouble` and `paddu` twice
    each and not 29 and 15 times: every embedding of this kernel in a stored
    program lowers all of it again.

    A window is 4 doublings and ONE addition that handles infinity on
    either side and opposite operands and no other special case, because
    equal operands cannot arise: the windows descend, so before a window
    the accumulator is 16 m P, m the digits read so far, and the addend
    d P with d in 1..15; for m >= 1, 0 < 16 m - d and 16 m + d <= k < n, so
    they are neither the same nor opposite points when P has order n (G1
    has prime order, so every P but infinity has) and k < n. k is made
    canonical first (n / 2^255 > 1: one subtraction), so that holds for
    every 256-bit input. The same inside the table build: 2 j P + P with
    2 j < 16. P at infinity makes every entry infinity: the selects' case."""
    group = make_group(TILE_FIELD)
    pdouble, paddu = group.pdouble, group.paddu
    W = dig_ref.shape[0]

    def load(ref, *at):
        return tuple([ref[at + (c, l)] for l in range(NL)] for c in range(3))

    def store(ref, pt, *at):
        for c in range(3):
            for l in range(NL):
                ref[at + (c, l)] = pt[c][l]

    k = tile_canonical_scalar([k_ref[l] for l in range(NL)])
    for w in range(W):                        # row 0 the top digit
        limb, s = divmod(W - 1 - w, 4)
        dig_ref[w] = (k[limb] >> np.uint32(4 * s)) & np.uint32(0xF)

    store(tab_ref, load(p_ref), 0)

    def build(j, _):
        # T[2j] = 2 T[j], T[2j+1] = T[2j] + P, j in 1..7 (T[d] at d - 1)
        even = pdouble(load(tab_ref, j - 1))
        store(tab_ref, even, 2 * j - 1)
        store(tab_ref, paddu(even, load(p_ref)), 2 * j)

    # int32 bounds: with jax_enable_x64 a python-int fori_loop carries an
    # i64 induction var, which Mosaic cannot lower
    jax.lax.fori_loop(jnp.int32(1), jnp.int32(8), build, None)

    def select(d):
        # per-lane table lookup: 15 whole-vreg selects a word
        masks = [d == np.uint32(v) for v in range(1, 16)]
        inf = _tile_inf_like(([d] * NL,) * 3)
        out = []
        for c in range(3):
            words = []
            for l in range(NL):
                word = inf[c][l]
                for v in range(1, 16):
                    word = jnp.where(masks[v - 1], tab_ref[v - 1, c, l], word)
                words.append(word)
            out.append(words)
        return tuple(out)

    def body(w, acc):
        acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(4),
                                lambda _, a: pdouble(a), acc)
        return paddu(acc, select(dig_ref[w]))

    acc = jax.lax.fori_loop(jnp.int32(1), jnp.int32(W), body,
                            select(dig_ref[0]))
    store(o_ref, acc)


@functools.partial(jax.jit, static_argnames=("n_windows", "interpret"))
def _scalar_mul_flat(p, k, n_windows: int, interpret: bool):
    N = p.shape[0]
    n_tiles = max((N + TILE_LANES - 1) // TILE_LANES, 1)
    Np = n_tiles * TILE_LANES
    rows = Np // LANES
    # (N, ..., 16) -> (..., 16, Np / 128, 128): lane n of the batch is row
    # n // 128, column n % 128 of every limb's plane, one transpose either
    # way, as `_fixed_base_mul_flat` lays its scalars out
    pt = jnp.transpose(
        _pad_lanes(p, Np, axis=0).reshape(rows, LANES, 3, NL), (2, 3, 0, 1))
    kt = jnp.transpose(_pad_lanes(k, Np, axis=0).reshape(rows, LANES, NL),
                       (2, 0, 1))
    pt_spec = pl.BlockSpec((3, NL, 8, LANES), lambda i: (0, 0, i, 0),
                           memory_space=pltpu.VMEM)
    # x64 mode would make BlockSpec index maps / loop bounds i64, which
    # Mosaic cannot legalize; every value here is uint32, so drop to x32
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _scalar_mul_kernel,
            grid=(n_tiles,),
            in_specs=[
                pt_spec,
                pl.BlockSpec((NL, 8, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pt_spec,
            out_shape=jax.ShapeDtypeStruct((3, NL, rows, LANES), jnp.uint32),
            scratch_shapes=[pltpu.VMEM((15, 3, NL, 8, LANES), jnp.uint32),
                            pltpu.VMEM((n_windows, 8, LANES), jnp.uint32)],
            interpret=interpret,
        )(pt, kt)
    return jnp.transpose(out, (2, 3, 0, 1)).reshape(Np, 3, NL)[:N]


def scalar_mul_flat(p, k, n_windows: int = 64):
    """k*P batched: p (N, 3, 16) Jacobian Montgomery, k (N, 16) plain
    scalars, any 256-bit value (reduced mod n inside) -> (N, 3, 16). Pads N
    up to a TILE_LANES multiple and tiles. n_windows < 64 truncates the
    ladder for short scalars (k < 16^W)."""
    return _scalar_mul_flat(p, k, n_windows, INTERPRET)


# ---------------------------------------------------------------------------
# Fixed-base windowed mult kernel: shared (64, 16)-entry table, add-only
# ---------------------------------------------------------------------------

_ENTRY = 2 * NL + 1               # words a table entry: x, y, is-infinity


def _fixed_base_kernel(tab_ref, k_ref, o_ref, dig_ref):
    """On limb tiles (TILE_FIELD): k_ref (16, 8, 128), o_ref (3, 16, 8, 128),
    dig_ref (W, 8, 128). tab_ref: flat in SMEM, entry (w, v) at
    (w * 16 + v) * _ENTRY: the 16 limbs of x, the 16 of y, then 1 where the
    entry is the point at infinity, of the precomputed AFFINE points
    v * 16^w * P (infinity: the v=0 entry, or every entry of a table of the
    point at infinity). W mixed (Jacobian + affine) additions, no doubles
    (the 16^w factors are baked in); W < 64 serves scalars known to be
    < 16^W (small plaintexts).

    The window step handles infinity on either side and no other special
    case, because none can arise: the windows ascend, so at window w the
    accumulator is (k mod 16^w) * P and the addend d * 16^w * P with d in
    1..15; they are the same or opposite points only if d * 16^w -/+
    (k mod 16^w) is a multiple of the group order n, which k < n excludes
    (n / 2^252 = 8.98 and G1 has prime order). k is made canonical first,
    so that holds for every 256-bit input."""
    pmadd = make_group(TILE_FIELD).pmadd
    k = tile_canonical_scalar([k_ref[l] for l in range(NL)])
    W = dig_ref.shape[0]
    for w in range(W):                        # little-endian digit order
        limb, s = divmod(w, 4)
        dig_ref[w] = (k[limb] >> np.uint32(4 * s)) & np.uint32(0xF)

    def body(w, acc):
        d = dig_ref[w]                        # (8, 128)
        base = w * np.int32(16 * _ENTRY)
        masks = [d == np.uint32(v) for v in range(1, 16)]

        def sel(word):
            # per-lane digit select of one word of the window's 16
            # entries, each a scalar: 15 whole-vreg selects
            out = jnp.full(d.shape, tab_ref[base + word], jnp.uint32)
            for v in range(1, 16):
                out = jnp.where(masks[v - 1],
                                tab_ref[base + (v * _ENTRY + word)], out)
            return out

        return pmadd(acc, [sel(l) for l in range(NL)],
                     [sel(NL + l) for l in range(NL)], sel(2 * NL) != 0)

    acc0 = _tile_inf_like((k, k, k))
    acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(W), body, acc0)
    for c in range(3):
        for l in range(NL):
            o_ref[c, l] = acc[c][l]


def _flat_table(table):
    """(W, 16, 3, 16) window table -> the kernel's flat run of _ENTRY words
    an entry (w, v): x's limbs, y's limbs, 1 where Z is zero. The table
    comes from the caller (elgamal.FixedBase), so pin uint32 here as
    `_pad_lanes` does."""
    tab = jnp.asarray(table, dtype=jnp.uint32)
    q_inf = jnp.all(tab[:, :, 2] == 0, axis=-1).astype(jnp.uint32)
    return jnp.concatenate([tab[:, :, 0], tab[:, :, 1], q_inf[..., None]],
                           axis=-1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("n_windows", "interpret"))
def _fixed_base_mul_flat(table, k, n_windows: int, interpret: bool):
    N = k.shape[0]
    W = n_windows
    n_tiles = max((N + TILE_LANES - 1) // TILE_LANES, 1)
    Np = n_tiles * TILE_LANES
    rows = Np // LANES
    # (N, 16) -> (16, Np / 128, 128): lane n of the batch is row n // 128,
    # column n % 128 of every limb's plane, one transpose either way
    kt = jnp.transpose(_pad_lanes(k, Np, axis=0).reshape(rows, LANES, NL),
                       (2, 0, 1))
    tt = _flat_table(table[:W])
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _fixed_base_kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((NL, 8, LANES), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((3, NL, 8, LANES),
                                   lambda i: (0, 0, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((3, NL, rows, LANES), jnp.uint32),
            scratch_shapes=[pltpu.VMEM((W, 8, LANES), jnp.uint32)],
            interpret=interpret,
        )(tt, kt)
    return jnp.transpose(out, (2, 3, 0, 1)).reshape(Np, 3, NL)[:N]


def fixed_base_mul_flat(table, k, n_windows: int = 64):
    """k*P via a shared fixed-base window table. table: (64, 16, 3, 16) as
    built by elgamal.FixedBase, whose entries are AFFINE (Z the Montgomery
    one, or zero for infinity): the kernel adds them with the mixed
    addition and reads of Z only whether it is zero. k: (N, 16) plain
    scalars, any 256-bit value (reduced mod n inside) -> (N, 3, 16)
    Jacobian. n_windows < 64 truncates the ladder for small scalars
    (k < 16^W)."""
    return _fixed_base_mul_flat(table, k, n_windows, INTERPRET)


# ---------------------------------------------------------------------------
# Batched complete point add + R-way reduce kernels
# ---------------------------------------------------------------------------

def _point_add_kernel(m_ref, np_ref, p_ref, q_ref, o_ref):
    m = m_ref[:]
    padd = make_group(sublane_field(m, np_ref[0, 0])).padd
    r = padd((p_ref[0], p_ref[1], p_ref[2]),
             (q_ref[0], q_ref[1], q_ref[2]))
    o_ref[0], o_ref[1], o_ref[2] = r


def _point_reduce_kernel(m_ref, np_ref, p_ref, o_ref):
    """p_ref: (R, 3, 16, B) — sum rows 0..R-1 with the complete group add,
    one row a step of a loop over the ref's leading axis, so that one
    `padd` is traced and lowered whatever R is."""
    m = m_ref[:]
    padd = make_group(sublane_field(m, np_ref[0, 0])).padd

    def body(r, acc):
        return padd(acc, (p_ref[r, 0], p_ref[r, 1], p_ref[r, 2]))

    acc = (p_ref[0, 0], p_ref[0, 1], p_ref[0, 2])
    acc = jax.lax.fori_loop(jnp.int32(1), jnp.int32(p_ref.shape[0]), body,
                            acc)
    o_ref[0], o_ref[1], o_ref[2] = acc


def _mk_point_io(n_tiles, Np, extra=None):
    specs = [
        pl.BlockSpec((NL, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
    ]
    if extra:
        specs += extra
    return dict(
        grid=(n_tiles,),
        in_specs=specs,
        out_specs=pl.BlockSpec((3, NL, LANES), lambda i: (0, 0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((3, NL, Np), jnp.uint32),
    )


def _pad_lanes(x, Np, axis=-1):
    # every Mosaic operand funnels through here: pin uint32 at the choke
    # point so a weak int32/i64 limb tensor can never reach a kernel
    x = jnp.asarray(x, dtype=jnp.uint32)
    N = x.shape[axis]
    if N == Np:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, Np - N)
    # pin the fill constant: a weak-typed 0 becomes i64 when traced with
    # x64 on, and mixing it into the x64-off pallas operand prep produces
    # a jaxpr that fails MLIR verification at lowering
    return jnp.pad(x, pad, constant_values=np.zeros((), x.dtype))


@functools.partial(jax.jit, static_argnames="interpret")
def _point_add_flat(p, q, interpret: bool):
    N = p.shape[0]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    pt = _pad_lanes(jnp.transpose(p, (1, 2, 0)), Np)
    qt = _pad_lanes(jnp.transpose(q, (1, 2, 0)), Np)
    m_in = jnp.asarray(_M_FP[:, None], dtype=jnp.uint32)
    np_in = jnp.asarray([[_NPRIME_FP]], dtype=jnp.uint32)
    io = _mk_point_io(n_tiles, Np, extra=[
        pl.BlockSpec((3, NL, LANES), lambda i: (0, 0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((3, NL, LANES), lambda i: (0, 0, i),
                     memory_space=pltpu.VMEM),
    ])
    with jax.enable_x64(False):
        out = pl.pallas_call(_point_add_kernel, interpret=interpret, **io)(m_in, np_in, pt, qt)
    return jnp.transpose(out, (2, 0, 1))[:N]


def point_add_flat(p, q):
    """Complete add, (N, 3, 16) x (N, 3, 16) -> (N, 3, 16)."""
    return _point_add_flat(p, q, INTERPRET)


@functools.partial(jax.jit, static_argnames="interpret")
def _point_reduce_flat(pts, interpret: bool):
    R, N = pts.shape[0], pts.shape[1]
    n_tiles = max((N + LANES - 1) // LANES, 1)
    Np = n_tiles * LANES
    pt = _pad_lanes(jnp.transpose(pts, (0, 2, 3, 1)), Np)  # (R,3,16,Np)
    m_in = jnp.asarray(_M_FP[:, None], dtype=jnp.uint32)
    np_in = jnp.asarray([[_NPRIME_FP]], dtype=jnp.uint32)
    io = _mk_point_io(n_tiles, Np, extra=[
        pl.BlockSpec((R, 3, NL, LANES), lambda i: (0, 0, 0, i),
                     memory_space=pltpu.VMEM),
    ])
    with jax.enable_x64(False):
        out = pl.pallas_call(_point_reduce_kernel, interpret=interpret, **io)(m_in, np_in, pt)
    return jnp.transpose(out, (2, 0, 1))[:N]


def point_reduce_flat(pts):
    """Group-add reduce over axis 0: (R, N, 3, 16) -> (N, 3, 16), one
    kernel call (replaces log2(R) jnp tree-reduce rounds; the DPs'
    aggregate, `service._fused_agg`, and flagship's reductions)."""
    return _point_reduce_flat(pts, INTERPRET)


def available() -> bool:
    """True when the Mosaic TPU path runs here (tests: DRYNX_NO_PALLAS=1).

    A backend that fails to initialise raises out of here: a chip that is
    busy or broken must not read as "no TPU", which would send the whole
    proof family to the host oracle and return a passing survey."""
    if os.environ.get("DRYNX_NO_PALLAS", "0") == "1":
        return False
    if INTERPRET:
        return True
    return jax.default_backend() == "tpu"


__all__ = ["scalar_mul_flat", "fixed_base_mul_flat", "point_add_flat",
           "point_reduce_flat", "mont_mul", "fadd", "fsub", "make_group",
           "Group",
           "canonical_scalar", "Field", "sublane_field", "TILE_FIELD",
           "tile_mont_mul", "tile_fadd", "tile_fsub", "tile_canonical_scalar",
           "available", "LANES", "TILE_LANES"]
