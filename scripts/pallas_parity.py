"""On-chip Pallas kernel parity vs the pure-Python oracle.

Round-4 VERDICT task 2 / missing #2: the round-4 Mosaic kernels (cyclotomic
squaring, windowed cyclotomic pows, the per-base window-table digit pow, the
16-window G1 ladder) shipped without ever executing on any backend —
interpret mode needs ~10 min PER KERNEL on this box class, so hardware is
the only realistic validator. Run me FIRST in any TPU session, before any
bench: every kernel gets a pass/fail/time line against crypto/refimpl (the
oracle every kernel is defined against), and the JSON verdict goes to
stdout AND chiprun_out/pallas_parity.json (what a chip call brings back).

Ordering: kernels that have never run on hardware at HEAD come FIRST, so a
session cut short by the driver still validates the highest-risk code.
Each check is individually contained — one kernel failing (or hanging the
lowering) must not erase the record of the ones before it (partial results
are flushed to chiprun_out/pallas_parity.json after every check).

Usage:  python scripts/pallas_parity.py  [--skip-slow] [--only NAME]
(--skip-slow drops the Miller/pair/final-exp family, whose lowering is the
expensive tail; the GT/ladder families alone validate everything new.
--only runs the checks whose name contains NAME, or one of several NAMEs
given with commas between them: the kernels a PR touched, at their
lowering cost and not the whole family's.)
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from drynx_tpu.utils.cache import enable_compilation_cache

enable_compilation_cache()

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = []
ONLY = ""
OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chiprun_out", "pallas_parity.json")


def flush():
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump({"backend": jax.default_backend(),
                   "checks": RESULTS}, f, indent=1)


def check(name, fn):
    if not any(part in name for part in ONLY.split(",")):
        return
    t0 = time.perf_counter()
    try:
        fn()
        rec = {"kernel": name, "ok": True,
               "seconds": round(time.perf_counter() - t0, 2)}
    except Exception as e:  # record and continue — partial evidence counts
        import traceback

        traceback.print_exc(limit=6)
        rec = {"kernel": name, "ok": False,
               "seconds": round(time.perf_counter() - t0, 2),
               "error": repr(e)[:300]}
    RESULTS.append(rec)
    print(f"[{rec['seconds']:7.1f}s] {name}: "
          f"{'ok' if rec['ok'] else 'FAIL ' + rec.get('error', '')}",
          file=sys.stderr, flush=True)
    flush()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-slow", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    global ONLY
    ONLY = args.only

    from drynx_tpu.crypto import batching as B
    from drynx_tpu.crypto import curve as C
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.crypto import field as F
    from drynx_tpu.crypto import fp12 as F12
    from drynx_tpu.crypto import host_oracle as ho
    from drynx_tpu.crypto import pallas_ops as po
    from drynx_tpu.crypto import pallas_pairing as pp
    from drynx_tpu.crypto import params, refimpl

    print("backend:", jax.default_backend(), file=sys.stderr, flush=True)
    assert po.available(), "no Pallas backend — this is the TPU validator"
    rng = np.random.default_rng(17)

    def rfp():
        return int.from_bytes(rng.bytes(40), "little") % params.P

    def rf12():
        return tuple((rfp(), rfp()) for _ in range(6))

    gt = refimpl.pair(refimpl.G1, refimpl.G2)          # canonical GΦ12 elt
    gt2 = refimpl.pair(refimpl.g1_mul(refimpl.G1, 7), refimpl.G2)
    d_gt = jnp.asarray(F12.from_ref(gt))
    d_gt2 = jnp.asarray(F12.from_ref(gt2))

    # ---------------- new-at-HEAD kernels first ----------------

    def c_csqr():
        got = F12.to_ref(pp.f12_csqr_flat(d_gt[None])[0])
        assert got == refimpl.fp12_sq(gt)

    check("f12_csqr_flat (cyclotomic squaring)", c_csqr)

    def c_wpow_cyc():
        for bits, e in [(256, rfp() % params.N), (63, 0x2FFFFFFFFFFFFFFF),
                        (128, params.P - params.N)]:
            k = jnp.asarray(F.from_int(e))[None]
            got = F12.to_ref(pp.f12_wpow_flat(
                d_gt[None], k, n_bits=bits, cyc=True)[0])
            assert got == refimpl.fp12_pow(gt, e), bits

    check("f12_wpow_flat cyc=True (256/63/128-bit)", c_wpow_cyc)

    def c_gt_pow_fixed_multi():
        from drynx_tpu.proofs import range_proof as rp

        sigs = [rp.init_range_sig(4, np.random.default_rng(3))
                for _ in range(2)]
        T = rp._sig_gt_pow_tables_dev(sigs)
        gtA = np.asarray(rp.sig_gt_table(sigs))
        es = [5, 12345, params.N - 2]
        base_idx = jnp.asarray([[0], [5]], dtype=jnp.int32)   # (ns=2, 1)
        k = jnp.asarray(F.from_int([es[1]]))[None]
        k2 = jnp.broadcast_to(k, (2, 1, 16))
        got = rp._gt_pow_multi(T, base_idx, k2)
        for i, b in enumerate([0, 5]):
            base = ho._fp12_to_ref(gtA[b // 4, b % 4])
            want = refimpl.fp12_pow(base, es[1])
            assert ho._fp12_to_ref(np.asarray(got[i, 0])) == want, i

    check("gt_pow_fixed_multi (window-table digit pow)", c_gt_pow_fixed_multi)

    def ladder_bases(n):
        # a base a lane, every eighth the point at infinity; few distinct
        # points, so that the Python oracle's bill is the multiplications'
        pool = [refimpl.g1_mul(refimpl.G1, 3 + i) for i in range(7)] + [None]
        return [pool[i % 8] for i in range(n)]

    def ladder_check(ks, n_windows, sampled):
        """`po.scalar_mul_flat` over the lanes of ks, a base a lane,
        against the oracle on the lanes `sampled` and at infinity
        everywhere a scalar is 0 mod n or the base is infinity."""
        n = params.N
        pts = ladder_bases(len(ks))
        got = po.scalar_mul_flat(jnp.asarray(C.from_ref_batch(pts)),
                                 jnp.asarray(F.from_int(ks)),
                                 n_windows=n_windows)
        assert got.shape == (len(ks), 3, 16)
        at_inf = ~np.asarray(got)[:, 2].any(axis=-1)
        want_inf = [p is None or k % n == 0 for p, k in zip(pts, ks)]
        assert at_inf.tolist() == want_inf
        ref = C.to_ref(got[np.asarray(sampled)])
        for i, r in zip(sampled, ref):
            want = None if pts[i] is None else refimpl.g1_mul(pts[i],
                                                              ks[i] % n)
            assert r == want, (i, hex(ks[i]))

    def c_ladder16():
        # 16 windows serve scalars below 16^16; 2 windows below 256
        small = [0, 1, 15, 16, (1 << 62) - 3, 0x1234567890ABCDEF,
                 16 ** 16 - 1, 16 ** 15, 0xF0F0 << 40]
        ks = small + [int.from_bytes(rng.bytes(8), "little")
                      for _ in range(po.TILE_LANES + 8)] + small
        edge = list(range(len(small))) + list(
            range(len(ks) - len(small), len(ks)))
        seam = list(range(po.TILE_LANES - 4, po.TILE_LANES + 4))
        ladder_check(ks, 16, edge + seam + [100, 500, 777])
        tiny = [0, 1, 15, 16, 17, 200, 255, 0xF0, 0x0F]
        ladder_check(tiny, 2, list(range(len(tiny))))

    check("scalar_mul_flat n_windows=16 (62-bit ladder)", c_ladder16)

    def c_slotmul():
        a = rf12()
        da = jnp.asarray(F12.from_ref(a))[None]
        for e in (1, 2, 3):
            got = F12.to_ref(pp.f12_slotmul_flat(da, f"frob{e}")[0])
            assert got == ho._fp12_frob(a, e), e
        got = F12.to_ref(pp.f12_slotmul_flat(da, "conj6")[0])
        assert got == refimpl.fp12_conj6(a)

    check("f12_slotmul_flat frob1/2/3 + conj6", c_slotmul)

    def c_order_gate():
        # the full soundness gate pair on-device: honest passes, a
        # cofactor root of unity passes membership but fails order-n
        assert B.gt_membership_ok(d_gt[None])
        assert B.gt_order_ok(d_gt[None])
        eps = jnp.asarray(F12.from_ref(refimpl.gphi12_cofactor_element(13)))
        assert B.gt_membership_ok(eps[None])
        assert not B.gt_order_ok(eps[None])

    check("gt_membership_ok + gt_order_ok (device dispatch)", c_order_gate)

    # ---------------- previously-validated kernel families ----------------

    def c_f12_mul_inv():
        a, b = rf12(), rf12()
        da = jnp.asarray(F12.from_ref(a))[None]
        db = jnp.asarray(F12.from_ref(b))[None]
        assert F12.to_ref(pp.f12_mul_flat(da, db)[0]) == refimpl.fp12_mul(a, b)
        inv = pp.f12_inv_flat(da)
        assert refimpl.fp12_mul(F12.to_ref(inv[0]), a) == refimpl.FP12_ONE

    check("f12_mul_flat + f12_inv_flat", c_f12_mul_inv)

    def c_mulreduce8():
        els = [rf12() for _ in range(8)]
        d = jnp.asarray(np.stack([F12.from_ref(e) for e in els]))[None]
        got = F12.to_ref(pp.f12_mulreduce8_flat(d)[0])
        want = els[0]
        for e in els[1:]:
            want = refimpl.fp12_mul(want, e)
        assert got == want

    check("f12_mulreduce8_flat (8-way GT product)", c_mulreduce8)

    def c_ladder64():
        # the kernel reduces k mod n first and adds without the doubling
        # case: the edges of both over two 1 024-lane tiles (at the first
        # tile's start, across the seam, at the second tile's end in its
        # padded last rows), every eighth base the point at infinity
        n = params.N
        edges = [0, 1, 2, 12345, n - 1, n, n + 1, 2 ** 256 - 1,
                 (8 << 252) + 12345, 15 << 248, 16 ** 63, 0xF0F0 << 100,
                 (n - 1) // 2, n - 16, n + 15, 0xF]
        seam = [n - 1, n, n + 1, 2 ** 256 - 1, 1, (8 << 252) + 12345, 0,
                16 ** 63]
        ks = edges + [int.from_bytes(rng.bytes(32), "little")
                      for _ in range(po.TILE_LANES - len(edges) - 4)]
        ks += seam + [int.from_bytes(rng.bytes(32), "little")
                      for _ in range(72)] + edges
        assert len(ks) > po.TILE_LANES + 64
        sampled = (list(range(len(edges)))
                   + list(range(po.TILE_LANES - 4, po.TILE_LANES + 4))
                   + list(range(len(ks) - len(edges), len(ks)))
                   + [int(i) for i in rng.integers(16, len(ks) - 16, 24)])
        ladder_check(ks, 64, sampled)

    check("scalar_mul_flat (full 64-window ladder)", c_ladder64)

    def c_fixed_base():
        # the ladder adds affine table entries with the mixed addition and
        # reduces k mod n first: the edges of both, over two 1 024-lane
        # tiles (the edges again at the second tile's end, in its padded
        # last rows), for the generator's table and a public key's
        n = params.N
        edges = [0, 1, 2, 12345, n - 1, n, n + 1, 2 ** 256 - 1,
                 (8 << 252) + 12345, 15 << 248, 16 ** 63, 0xF0F0 << 100]
        ks = edges + [int.from_bytes(rng.bytes(32), "little")
                      for _ in range(po.TILE_LANES + 76)] + edges
        kd = jnp.asarray(F.from_int(ks))
        pub = refimpl.g1_mul(refimpl.G1, rfp() % n)
        for base, tbl in [(refimpl.G1, eg.BASE_TABLE),
                          (pub, eg.pub_table(pub))]:
            got = C.to_ref(po.fixed_base_mul_flat(tbl.table, kd))
            for i, k in enumerate(ks):
                assert got[i] == refimpl.g1_mul(base, k % n), (i, hex(k))
        small = [0, 1, 15, 16, 200, 16 ** 16 - 1, 0x1234567890ABCDEF]
        got = C.to_ref(po.fixed_base_mul_flat(
            eg.BASE_TABLE.table, jnp.asarray(F.from_int(small)),
            n_windows=16))
        for i, k in enumerate(small):
            assert got[i] == refimpl.g1_mul(refimpl.G1, k), (i, hex(k))
        got = C.to_ref(po.fixed_base_mul_flat(
            eg.BASE_TABLE.table, jnp.asarray(F.from_int(small[:5])),
            n_windows=2))
        assert got == [refimpl.g1_mul(refimpl.G1, k) for k in small[:5]]
        got = C.to_ref(po.fixed_base_mul_flat(
            eg.FixedBase(None).table, kd[:8]))
        assert got == [None] * 8

    check("fixed_base_mul_flat", c_fixed_base)

    def c_dro_permute_add():
        # one 4 096-wide slab of a node's pass (parallel/dro.py) out of a
        # list of two slabs, the kernel path against the jnp addition limb
        # for limb, with every branch of the complete addition planted:
        # infinity left, right and on both sides, equal operands in the
        # same and in another representation, opposite operands
        from drynx_tpu.parallel import dro

        n, size = dro.CHUNK, 2 * dro.CHUNK
        key = jax.random.PRNGKey(31)
        ks = eg.random_scalars(key, (2 * size + 2 * n,))
        pts = np.array(eg.fixed_base_mul(eg.BASE_TABLE.table, ks))
        cts = pts[:2 * size].reshape(size, 2, 3, 16)
        zero = pts[2 * size:].reshape(n, 2, 3, 16)
        idx = np.asarray(jax.random.permutation(key, size))[:n]
        inf = np.asarray(C.infinity())
        same_point = C.from_ref(C.to_ref(cts[idx[4], 0]))     # with Z = 1
        for c, at in ((0, 0), (1, n - 8)):      # first and last tile
            i = at + np.arange(8)
            cts[idx[i[0]], c] = inf
            zero[i[1], c] = inf
            cts[idx[i[2]], c] = zero[i[2], c] = inf
            zero[i[3], c] = cts[idx[i[3]], c]
            zero[i[5], c] = np.asarray(C.neg(jnp.asarray(cts[idx[i[5]], c])))
        zero[4, 0] = same_point
        cts, idx, zero = map(jnp.asarray, (cts, idx, zero))
        got = np.asarray(dro._dro_permute_add(cts, idx, zero))
        want = np.asarray(eg.ct_add(jnp.take(cts, idx, axis=0), zero))
        off = np.argwhere((got != want).any(axis=(2, 3)))
        assert off.size == 0, off[:8].tolist()
        assert C.to_ref(got[4, 0]) == C.to_ref(C.double(cts[idx[4], 0]))
        at_inf = np.argwhere(~got[:, :, 2].any(axis=-1)).tolist()
        assert at_inf == [[2, 0], [5, 0], [n - 6, 1], [n - 3, 1]], at_inf

    check("dro_permute_add (point_add_flat under the noise phase's slab)",
          c_dro_permute_add)

    def c_g2_ladder():
        ks = [1, 7, params.N - 1]
        from drynx_tpu.crypto import g2 as G2

        q = refimpl.G2
        got = pp.g2_scalar_mul_flat(
            jnp.asarray(np.stack([G2.from_ref(q)] * len(ks))),
            jnp.asarray(F.from_int(ks)))
        for i, k in enumerate(ks):
            assert G2.to_ref(got[i]) == refimpl.g2_mul(q, k), i

    check("g2_scalar_mul_flat", c_g2_ladder)

    if not args.skip_slow:
        m_ref = refimpl.ate_miller_loop(refimpl.g1_mul(refimpl.G1, 9),
                                        refimpl.G2)

        def c_final_exp():
            dm = jnp.asarray(F12.from_ref(m_ref))[None]
            got = F12.to_ref(pp.final_exp_flat(dm)[0])
            assert got == ho.final_exp_fast(m_ref)

        check("final_exp_flat", c_final_exp)

        def c_pair():
            p = refimpl.g1_mul(refimpl.G1, 9)
            px = jnp.asarray(F.from_int([p[0] * params.R % params.P]))
            py = jnp.asarray(F.from_int([p[1] * params.R % params.P]))
            from drynx_tpu.crypto import g2 as G2

            qd = G2.from_ref(refimpl.G2)
            qx = jnp.asarray(qd[0][None])
            qy = jnp.asarray(qd[1][None])
            got = F12.to_ref(pp.pair_flat(px, py, qx, qy)[0])
            assert got == refimpl.pair(p, refimpl.G2)

        check("pair_flat (full reduced pairing)", c_pair)

        def c_miller_then_fe():
            # Miller values differ by Fp line factors the final exp kills
            p = refimpl.g1_mul(refimpl.G1, 9)
            px = jnp.asarray(F.from_int([p[0] * params.R % params.P]))
            py = jnp.asarray(F.from_int([p[1] * params.R % params.P]))
            from drynx_tpu.crypto import g2 as G2

            qd = G2.from_ref(refimpl.G2)
            m = pp.miller_flat(px, py, jnp.asarray(qd[0][None]),
                               jnp.asarray(qd[1][None]))
            got = F12.to_ref(pp.final_exp_flat(m)[0])
            assert got == refimpl.pair(p, refimpl.G2)

        check("miller_flat -> final_exp_flat", c_miller_then_fe)

    n_fail = sum(1 for r in RESULTS if not r["ok"])
    flush()
    print(json.dumps({"metric": "pallas_kernel_parity",
                      "checks": len(RESULTS), "failed": n_fail,
                      "record": OUT_PATH}))
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
