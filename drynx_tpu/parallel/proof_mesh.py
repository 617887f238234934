"""Mesh-sharded range-proof verification: the RLC batch check's expensive
work (Miller loops + GT exponentiations) distributed over a device mesh.

The reference's dominant cost is VN range-proof verification (21.73 s per
proofs-on query across 7 VN machines, BASELINE.md timeline row). The TPU
answer is the same shape as every other hot path here: the per-digit pairing
work is a flat batch, so it shards over mesh axes and combines with a
custom GT-multiplication all-reduce. One VN with an n-device slice verifies
n times faster; the randomized accept decision is unchanged.

Checked identity (verify_range_proofs_batch, proofs/range_proof.py):

  finalexp( prod_ij M(r_ij*(c*y_i - Zphi_j*B), V_ij) )
    * prod_ij conj6(a_ij)^r_ij * gtB^(sum_ij r_ij*Zv_ij)  ==  1

The Miller products and conj6(a)^r products reduce per-shard, then the
partials combine with one GT multiplication tree; the single shared final
exponentiation runs once — it is one element, not worth a collective.
Exactness: bit-identical GT total vs the single-device path
(tests/test_proof_mesh.py) — Montgomery F12 multiplication is exact mod-p
with canonical representatives, so any grouping of the partial products
yields identical limb arrays.

Two execution strategies:

  * `rlc_total_shards` (DEFAULT, strategy="chunked") — per-device chunk
    dispatch through the SAME single-device bucketed programs
    (batching.miller / gt_pow64 / gt_reduce_prod) at the per-shard bucket,
    so the compilecache registry covers it (registry._shard_schemas) and
    every backend keeps its normal routing (host-oracle detour on CPU,
    Mosaic kernels on TPU with each shard device_put on its own device and
    async dispatch overlapping the mesh).
  * `rlc_total_sharded` (strategy="spmd") — the original
    jit(shard_map(...)) program with the GT all-reduce riding ICI inside
    one XLA program. Kept for on-chip use (slow-tier test): its body stays
    traceable, so on CPU it cannot take the host-oracle detour and one
    monolithic compile exceeds 90 min on the 1-core box.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..crypto import curve as C
from ..crypto import fp12 as F12
from ..crypto import pairing as PAIR
from ..crypto import params
from . import collective as col


def _flatten_pad(n_dev: int, *arrs):
    """Flatten leading (ns, V, l) dims to N, edge-pad N up to a multiple of
    n_dev (padded lanes are masked out of the products)."""
    N = int(np.prod(arrs[0].shape[:3]))
    Np = ((N + n_dev - 1) // n_dev) * n_dev
    out = []
    for a in arrs:
        a = jnp.asarray(a).reshape((N,) + a.shape[3:])
        if Np != N:
            pad = jnp.broadcast_to(a[:1], (Np - N,) + a.shape[1:])
            a = jnp.concatenate([a, pad], axis=0)
        out.append(a)
    mask = (jnp.arange(Np) < N)
    return out, mask, N


def rlc_total_sharded(mesh, proof, sigs_pub, r_int, gtb_pow_s):
    """The RLC check's GT total, computed over `mesh` (all axes flattened).

    proof: a RangeProofBatch; sigs_pub: per-CN affine publics; r_int:
    int64 (ns, V, l) verifier weights; gtb_pow_s: gtB^(sum r*Zv), (6,2,16)
    (one fixed-base power, computed by the caller). Returns the (6, 2, 16)
    GT total — equals F12.one() iff the batch verifies.
    """
    # verification is one flat batch — re-view the same devices as a 1-D
    # mesh so the GT all-reduce runs over a single named axis
    devs = np.asarray(mesh.devices).reshape(-1)
    n_dev = int(devs.size)
    flat_mesh = jax.sharding.Mesh(devs, ("vnshard",))

    ys = jnp.asarray(np.stack([C.from_ref(p) for p in sigs_pub]))
    c, zphi = jnp.asarray(proof.challenge), jnp.asarray(proof.zphi)

    # cheap G1 prep (full batch, unsharded): g1arg = r*(c*y_i - Zphi_j*B)
    from ..crypto import batching as B
    from ..crypto import elgamal as eg

    r = B.int_to_scalar(jnp.asarray(r_int))                    # (ns, V, l, 16)
    cy = B.g1_scalar_mul(ys[:, None, :, :], c[None, :, :])
    nzphiB = B.fixed_base_mul(eg.BASE_TABLE.table, B.fn_neg(zphi))
    g1arg = B.g1_add(cy[:, :, None, :, :], nzphiB[None])       # (ns, V, l, 3, 16)
    g1arg_r = B.g1_scalar_mul64(g1arg, r)   # 62-bit weights: short ladder
    px, py, _ = B.g1_normalize(g1arg_r)
    qx, qy, _ = B.g2_normalize(jnp.asarray(proof.v_pts))
    conj_a = F12.conj6(jnp.asarray(proof.a))

    (px, py, qx, qy, ca, rr), mask, _ = _flatten_pad(
        n_dev, px, py, qx, qy, conj_a, r)

    spec = P("vnshard")

    from ..crypto import pallas_ops as po
    from ..crypto import pallas_pairing as ppair

    def shard(px, py, qx, qy, ca, rr, mask):
        # per-shard Miller loops + conj6(a)^r, masked partial products
        m = PAIR.miller_loop((px, py), (qx, qy))
        if po.available():
            # 63-bit windowed pow — same kernel the single-device verifier
            # uses for the 62-bit RLC weights (batching.gt_pow64); cyc is
            # safe: rlc_prelude gated a through gt_membership_ok
            ar = ppair.f12_wpow_flat(ca, rr, n_bits=63, cyc=True)
        else:
            # 63-bit truncated scan: the weights are 62-bit and the full
            # 256-step graph quadruples the (already heavy) shard compile
            ar = F12.pow_var(ca, rr, n_bits=63)
        one = jnp.broadcast_to(jnp.asarray(F12.one()), m.shape)
        mk = mask[:, None, None, None]
        m = jnp.where(mk, m, one)
        ar = jnp.where(mk, ar, one)

        def prod(x):
            while x.shape[0] > 1:
                half = x.shape[0] // 2
                red = F12.mul(x[: 2 * half : 2], x[1 : 2 * half : 2])
                x = (jnp.concatenate([red, x[-1:]], axis=0)
                     if x.shape[0] % 2 else red)
            return x[0]

        m_p, a_p = prod(m), prod(ar)
        # GT-multiplication all-reduce over the whole mesh (ICI butterfly)
        m_tot = col._allreduce(m_p, "vnshard", n_dev, F12.mul)
        a_tot = col._allreduce(a_p, "vnshard", n_dev, F12.mul)
        return m_tot, a_tot

    f = jax.jit(shard_map(
        shard, mesh=flat_mesh,
        in_specs=(spec, spec, spec, spec, spec, spec, spec),
        out_specs=(P(), P()), check_vma=False))
    m_tot, a_tot = f(px, py, qx, qy, ca, rr,
                     mask.astype(jnp.uint32))
    fe = PAIR.final_exp(m_tot[None])[0]
    return F12.mul(F12.mul(fe, a_tot), jnp.asarray(gtb_pow_s))


def _g1_prep(proof, sigs_pub, r_int):
    """The cheap full-batch G1/G2 prep shared by both strategies:
    g1arg_r = r*(c*y_i - Zphi_j*B) normalized to affine, plus the affine
    V points and conj6(a). Returns device arrays shaped (ns, V, l, ...)."""
    from ..crypto import batching as B
    from ..crypto import elgamal as eg

    ys = jnp.asarray(np.stack([C.from_ref(p) for p in sigs_pub]))
    c, zphi = jnp.asarray(proof.challenge), jnp.asarray(proof.zphi)

    r = B.int_to_scalar(jnp.asarray(r_int))                    # (ns, V, l, 16)
    cy = B.g1_scalar_mul(ys[:, None, :, :], c[None, :, :])
    nzphiB = B.fixed_base_mul(eg.BASE_TABLE.table, B.fn_neg(zphi))
    g1arg = B.g1_add(cy[:, :, None, :, :], nzphiB[None])       # (ns, V, l, 3, 16)
    g1arg_r = B.g1_scalar_mul64(g1arg, r)   # 62-bit weights: short ladder
    px, py, _ = B.g1_normalize(g1arg_r)
    qx, qy, _ = B.g2_normalize(jnp.asarray(proof.v_pts))
    conj_a = F12.conj6(jnp.asarray(proof.a))
    return px, py, qx, qy, conj_a, r


def rlc_total_shards(proof, sigs_pub, r_int, gtb_pow_s,
                     n_shards: int | None = None,
                     phase: str = "VerifyShard"):
    """The RLC check's GT total via per-device chunk dispatch (the default
    mesh strategy — see module docstring). Bit-identical to the
    single-device `range_proof.rlc_total_single`: the same bucketed
    programs compute the same per-element values, and the partial-product
    regrouping is exact.

    Each VN-role shard runs Miller loops + conj6(a)^r pows over its slice
    of the flattened (ns*V*l) digit batch and reduces locally; partials
    combine with one gt_reduce_prod tree, then the single shared final
    exponentiation and gtB power fold in exactly as on one device.

    phase: SHARD_TIMERS span label — the cross-survey scheduler passes
    "CrossSurveyVerifyShard" so its batched dispatches attribute
    separately from per-survey "VerifyShard" spans.
    """
    from ..crypto import batching as B
    from . import proof_plane as plane

    if n_shards is None:
        n_shards = plane.n_shards()

    px, py, qx, qy, conj_a, r = _g1_prep(proof, sigs_pub, r_int)
    N = int(np.prod(px.shape[:3]))

    def flat(x):
        return jnp.asarray(x).reshape((N,) + x.shape[3:])

    px, py, qx, qy, ca, rr = map(flat, (px, py, qx, qy, conj_a, r))
    slices = plane.shard_slices(N, n_shards)

    def stage_total(i, a, b):
        # input staging: the per-shard slices are one-shot, so their
        # buffers are donated to the upload (reused where the backend
        # can alias); uploads overlap the previous shard's compute
        return plane.put_shard(
            (px[a:b], py[a:b], qx[a:b], qy[a:b], ca[a:b], rr[a:b]), i,
            donate=True)

    def shard_total(i, spx, spy, sqx, sqy, sca, srr):
        m = B.miller(spx, spy, sqx, sqy)
        # 63-bit windowed pow — same program the single-device verifier
        # uses for the 62-bit RLC weights; a passed the prelude's
        # membership/order gates, so the cyclotomic fast path is sound
        ar = B.gt_pow64(sca, srr)
        nl = m.shape[-1]
        return (B.gt_reduce_prod(m.reshape(-1, 6, 2, nl)),
                B.gt_reduce_prod(ar.reshape(-1, 6, 2, nl)))

    parts = plane.dispatch_shards(
        phase, shard_total, [(a, b) for (a, b) in slices],
        prefetch=stage_total)
    # combine partials exactly as the single-device path combines its two
    # full-batch products: final_exp on the Miller product ONLY, then the
    # a-product and the gtB power fold in with plain GT muls
    m_tot = B.gt_reduce_prod(jnp.stack([p[0] for p in parts]))
    a_tot = B.gt_reduce_prod(jnp.stack([p[1] for p in parts]))
    fe = B.final_exp(m_tot[None])
    return B.gt_mul(B.gt_mul(fe, a_tot[None]),
                    jnp.asarray(gtb_pow_s)[None])[0]


def rlc_verify_sharded(proof, sigs_pub, ca_pub_table,
                       rng: np.random.Generator | None = None, *,
                       mesh=None, n_shards: int | None = None,
                       strategy: str = "auto",
                       phase: str = "VerifyShard") -> bool:
    """Mesh-parallel single-verdict verification of a RangeProofBatch —
    the DEFAULT joint-range path whenever the proof plane is enabled
    (proofs/range_proof.py `_safe_batch_verify` routes here).

    Same acceptance predicate as verify_range_proofs_batch (including the
    per-value D equation, the binding Fiat-Shamir challenge recompute and
    the GT membership/order gates, all in the shared rlc_prelude) — only
    the pairing-heavy RLC total is sharded, and it is bit-identical to
    the single-device total, so tamper-rejection semantics are unchanged.

    strategy: "auto"/"chunked" = per-device chunk dispatch (default);
    "spmd" = the monolithic shard_map program (requires `mesh`).
    """
    from ..proofs import range_proof as rp

    # SHARED preamble with the single-device verifier (rlc_prelude keeps
    # the D equation, challenge binding and weight draw in one place)
    pre_ok, r_int, gtb_pow_s = rp.rlc_prelude(
        proof, sigs_pub, ca_pub_table, rng=rng)
    if not pre_ok:
        return False

    if strategy == "spmd":
        if mesh is None:
            raise ValueError("strategy='spmd' needs an explicit mesh")
        total = rlc_total_sharded(mesh, proof, sigs_pub, r_int, gtb_pow_s)
    else:
        total = rlc_total_shards(proof, sigs_pub, r_int, gtb_pow_s,
                                 n_shards=n_shards, phase=phase)
    return bool(np.asarray(F12.eq(total, jnp.asarray(F12.one()))))


__all__ = ["rlc_total_sharded", "rlc_total_shards", "rlc_verify_sharded"]
