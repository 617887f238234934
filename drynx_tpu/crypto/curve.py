"""Batched G1 (bn256, y^2 = x^3 + 3) group ops on TPU.

Replaces kyber's per-object point arithmetic (used throughout the reference,
e.g. ElGamal ops in unlynx CipherText, obfuscation scalar mults at
protocols/obfuscation_protocol.go:241-243) with fixed-shape, branch-free
Jacobian-coordinate tensor math over the Montgomery field layer.

Point representation: uint32 array (..., 3, 16) = (X, Y, Z) Jacobian limbs in
Montgomery form; the point at infinity has Z == 0 (X/Y arbitrary nonzero).
Scalar multiplication is a 256-step `lax.scan` (double-and-add-always with
selects — constant shape, constant time), replacing data-dependent loops.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import field as F
from . import params, refimpl
from .field import FP
from .params import NUM_LIMBS


# ---------------------------------------------------------------------------
# Host helpers: oracle (affine int) <-> device (Jacobian limbs)
# ---------------------------------------------------------------------------

def from_ref(pt) -> np.ndarray:
    """Oracle affine point (or None) -> (3, 16) Jacobian Montgomery limbs."""
    if pt is None:
        x, y, z = 1, 1, 0
    else:
        x, y = pt
        z = 1
    mont = lambda v: params.to_limbs(v * params.R % params.P)
    return np.asarray([mont(x), mont(y), mont(z)], dtype=np.uint32)


def from_ref_batch(pts) -> np.ndarray:
    return np.stack([from_ref(p) for p in pts])


def to_ref(pt):
    """(..., 3, 16) device point(s) -> oracle affine point / list of points."""
    mx, my, inf = normalize(jnp.asarray(pt, dtype=jnp.uint32))
    aff_x = np.asarray(F.from_mont(mx, FP))
    aff_y = np.asarray(F.from_mont(my, FP))
    inf = np.asarray(inf)
    xs, ys = F.to_int(aff_x), F.to_int(aff_y)
    if np.asarray(inf).ndim == 0:
        return None if bool(inf) else (int(xs), int(ys))
    flat_inf = np.asarray(inf).reshape(-1)
    flat_x = np.asarray(xs, dtype=object).reshape(-1)
    flat_y = np.asarray(ys, dtype=object).reshape(-1)
    out = [None if i else (int(x), int(y)) for i, x, y in zip(flat_inf, flat_x, flat_y)]
    return out


# ---------------------------------------------------------------------------
# Device constants
# ---------------------------------------------------------------------------

def _const(pt):
    return jnp.asarray(from_ref(pt), dtype=jnp.uint32)


def infinity(batch_shape=()):
    base = jnp.asarray(from_ref(None), dtype=jnp.uint32)
    return jnp.broadcast_to(base, batch_shape + (3, NUM_LIMBS))


G1_GEN = _const(refimpl.G1)


# ---------------------------------------------------------------------------
# Group law
# ---------------------------------------------------------------------------

def is_infinity(p):
    return F.is_zero(p[..., 2, :])


@jax.jit
def double(p):
    """Jacobian doubling (a = 0): dbl-2009-l formulas."""
    X, Y, Z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    mul = lambda a, b: F.mont_mul(a, b, FP)
    A = mul(X, X)
    B = mul(Y, Y)
    C = mul(B, B)
    t = F.sub(mul(F.add(X, B), F.add(X, B)), F.add(A, C))
    D = F.add(t, t)
    E = F.add(F.add(A, A), A)
    Fv = mul(E, E)
    X3 = F.sub(Fv, F.add(D, D))
    C8 = F.add(F.add(F.add(C, C), F.add(C, C)), F.add(F.add(C, C), F.add(C, C)))
    Y3 = F.sub(mul(E, F.sub(D, X3)), C8)
    YZ = mul(Y, Z)
    Z3 = F.add(YZ, YZ)
    return jnp.stack([X3, Y3, Z3], axis=-2)


@jax.jit
def add(p, q):
    """Complete Jacobian addition via selects (add-2007-bl + edge cases)."""
    X1, Y1, Z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    X2, Y2, Z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]
    mul = lambda a, b: F.mont_mul(a, b, FP)

    Z1Z1 = mul(Z1, Z1)
    Z2Z2 = mul(Z2, Z2)
    U1 = mul(X1, Z2Z2)
    U2 = mul(X2, Z1Z1)
    S1 = mul(Y1, mul(Z2, Z2Z2))
    S2 = mul(Y2, mul(Z1, Z1Z1))
    H = F.sub(U2, U1)
    HH = F.add(H, H)
    I = mul(HH, HH)
    J = mul(H, I)
    r = F.sub(S2, S1)
    r = F.add(r, r)
    V = mul(U1, I)
    X3 = F.sub(F.sub(mul(r, r), J), F.add(V, V))
    SJ = mul(S1, J)
    Y3 = F.sub(mul(r, F.sub(V, X3)), F.add(SJ, SJ))
    ZZ = F.sub(F.sub(mul(F.add(Z1, Z2), F.add(Z1, Z2)), Z1Z1), Z2Z2)
    Z3 = mul(ZZ, H)
    res_add = jnp.stack([X3, Y3, Z3], axis=-2)

    res_dbl = double(p)

    p_inf = is_infinity(p)
    q_inf = is_infinity(q)
    h_zero = F.is_zero(H)
    r_zero = F.is_zero(r)

    sel = lambda c, t, f: jnp.where(c[..., None, None], t, f)
    out = sel(h_zero & r_zero & ~p_inf & ~q_inf, res_dbl, res_add)
    out = sel(h_zero & ~r_zero & ~p_inf & ~q_inf,
              infinity(out.shape[:-2]), out)
    out = sel(q_inf, p, out)
    out = sel(p_inf, q, out)
    return out


def sum_points(pts):
    """The sum over axis 0 of points (R, ..., 3, 16) -> (..., 3, 16).

    On a TPU one call of the Pallas reduce kernel (crypto/pallas_ops.py),
    every point of a row a lane, the rows added one after another;
    elsewhere `batching.tree_reduce_add`'s jnp log-depth tree. The two fold
    in other orders, so their Jacobian limbs differ and the group element
    does not: `topology.canon_points` makes the bytes one."""
    from . import pallas_ops as po
    from .batching import tree_reduce_add

    R = pts.shape[0]
    if po.available() and R > 1:
        out = po.point_reduce_flat(pts.reshape((R, -1, 3, NUM_LIMBS)))
        return out.reshape(pts.shape[1:])
    return tree_reduce_add(pts, add)


@jax.jit
def neg(p):
    Y = F.neg(p[..., 1, :], FP)
    return p.at[..., 1, :].set(Y)


def scalar_mul(p, k_limbs):
    """k * P. k_limbs: (..., 16) plain (non-Montgomery) scalar limbs.

    Dispatches to the Pallas ladder kernel on TPU (whole windowed ladder in
    one kernel on limb tiles, 1 024 lanes a tile — crypto/pallas_ops.py);
    elsewhere, the compact 256-step jnp ladder below (see its docstring for
    why the fallback is deliberately NOT windowed). Replaces kyber Point.Mul
    at e.g. reference lib/range/range_proof.go:326 and the ElGamal
    key-switch/decrypt sites.
    """
    from . import pallas_ops as po

    if po.available():
        batch = jnp.broadcast_shapes(p.shape[:-2], k_limbs.shape[:-1])
        pb = jnp.broadcast_to(p, batch + (3, NUM_LIMBS))
        kb = jnp.broadcast_to(k_limbs, batch + (NUM_LIMBS,))
        out = po.scalar_mul_flat(pb.reshape((-1, 3, NUM_LIMBS)),
                                 kb.reshape((-1, NUM_LIMBS)))
        return out.reshape(batch + (3, NUM_LIMBS))
    return _scalar_mul_jnp(p, k_limbs)


def scalar_mul_short(p, k_limbs, n_bits: int = 64):
    """k * P for SHORT scalars (k < 2^n_bits, e.g. the 62-bit RLC
    verification weights): the Pallas ladder runs ceil(n_bits/4) windows
    instead of 64 — 4x fewer ladder steps at n_bits=64. Semantics equal
    scalar_mul for in-range k; out-of-range high bits are simply ignored."""
    from . import pallas_ops as po

    if po.available():
        batch = jnp.broadcast_shapes(p.shape[:-2], k_limbs.shape[:-1])
        pb = jnp.broadcast_to(p, batch + (3, NUM_LIMBS))
        kb = jnp.broadcast_to(k_limbs, batch + (NUM_LIMBS,))
        out = po.scalar_mul_flat(pb.reshape((-1, 3, NUM_LIMBS)),
                                 kb.reshape((-1, NUM_LIMBS)),
                                 n_windows=(n_bits + 3) // 4)
        return out.reshape(batch + (3, NUM_LIMBS))
    return _scalar_mul_jnp_short(p, k_limbs, n_bits)


@partial(jax.jit, static_argnames="n_bits")
def _scalar_mul_jnp_short(p, k_limbs, n_bits: int):
    """Truncated fallback ladder: scan only the low n_bits (LSB-first)."""
    bits = (k_limbs[..., :, None]
            >> jnp.arange(params.LIMB_BITS, dtype=jnp.uint32)) & 1
    bits = bits.reshape(bits.shape[:-2] + (256,))[..., :n_bits]
    bits_t = jnp.moveaxis(bits, -1, 0)

    batch = jnp.broadcast_shapes(p.shape[:-2], k_limbs.shape[:-1])
    acc0 = infinity(batch)
    base0 = jnp.broadcast_to(p, batch + (3, NUM_LIMBS))

    def step(state, bit):
        acc, base = state
        acc2 = add(acc, base)
        acc = jnp.where(bit[..., None, None] == 1, acc2, acc)
        base = double(base)
        return (acc, base), None

    (acc, _), _ = jax.lax.scan(step, (acc0, base0), bits_t)
    return acc


@jax.jit
def _scalar_mul_jnp(p, k_limbs):
    """Fallback ladder: 256-step double-and-add-always scan (constant shape,
    constant time). Deliberately the COMPACT graph, not the windowed ladder:
    this path only runs where Pallas doesn't (CPU tests), and XLA's CPU
    pipeline both compiles the windowed ladder's 16-entry table graph for
    minutes per jit and has been seen segfaulting under the accumulated
    compile load. The TPU hot path is the Pallas windowed kernel above."""
    bits = (k_limbs[..., :, None]
            >> jnp.arange(params.LIMB_BITS, dtype=jnp.uint32)) & 1
    bits = bits.reshape(bits.shape[:-2] + (256,))
    bits_t = jnp.moveaxis(bits, -1, 0)  # (256, ...)

    batch = jnp.broadcast_shapes(p.shape[:-2], k_limbs.shape[:-1])
    acc0 = infinity(batch)
    base0 = jnp.broadcast_to(p, batch + (3, NUM_LIMBS))

    def step(state, bit):
        acc, base = state
        acc2 = add(acc, base)
        acc = jnp.where(bit[..., None, None] == 1, acc2, acc)
        base = double(base)
        return (acc, base), None

    (acc, _), _ = jax.lax.scan(step, (acc0, base0), bits_t)
    return acc


@jax.jit
def normalize(p):
    """Jacobian -> affine: returns (x, y, is_inf). x,y Montgomery limbs."""
    from . import pallas_ops as po

    X, Y, Z = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    inf = F.is_zero(Z)
    # avoid inv(0): substitute 1 for Z at infinity
    Zsafe = jnp.where(inf[..., None], FP.one_mont, Z)
    if po.available():
        # per-lane Fermat inversion kernel: the Montgomery-trick batch
        # inversion scans sequentially over the BATCH axis (slow on TPU)
        from . import pallas_pairing as ppair

        Zi = ppair.fp_inv_flat(Zsafe.reshape(-1, 16)).reshape(Zsafe.shape)
    else:
        Zi = F.batch_inv(Zsafe, FP)
    Zi2 = F.mont_mul(Zi, Zi, FP)
    x = F.mont_mul(X, Zi2, FP)
    y = F.mont_mul(Y, F.mont_mul(Zi, Zi2, FP), FP)
    return x, y, inf


@jax.jit
def eq(p, q):
    """Point equality in Jacobian coords (cross-multiplied, no inversion)."""
    X1, Y1, Z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    X2, Y2, Z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]
    mul = lambda a, b: F.mont_mul(a, b, FP)
    Z1Z1, Z2Z2 = mul(Z1, Z1), mul(Z2, Z2)
    same_x = F.eq(mul(X1, Z2Z2), mul(X2, Z1Z1))
    same_y = F.eq(mul(Y1, mul(Z2, Z2Z2)), mul(Y2, mul(Z1, Z1Z1)))
    p_inf, q_inf = is_infinity(p), is_infinity(q)
    return (p_inf & q_inf) | (~p_inf & ~q_inf & same_x & same_y)


def scalars_from_ints(ks) -> np.ndarray:
    """Python ints -> plain (non-Montgomery) scalar limb arrays mod N."""
    if isinstance(ks, (int,)):
        return F.from_int(ks % params.N)
    return F.from_int([k % params.N for k in ks])


__all__ = [
    "from_ref", "from_ref_batch", "to_ref", "infinity", "G1_GEN",
    "is_infinity", "double", "add", "neg", "scalar_mul", "scalar_mul_short",
    "normalize", "eq",
    "scalars_from_ints",
]
