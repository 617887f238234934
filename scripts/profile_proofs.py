"""Stage-level profiling of the range-proof pipeline at bench shape.

Times each sub-stage of creation (digit gather + G2 blinding, the per-digit
GT pow, the fixed-base gtB pow, canonical byte encode, Fiat-Shamir hash,
serialization) and of RLC verification (G1 weighting, Miller, a^r pow,
membership gate, shared final exp, gtB pow) separately, at the proofs-on
benchmark shape (10 DPs x V=90 x l=5 x ns=3 -> 13,500 digit proofs), plus
the keyswitch proof verify. One JSON line per stage on stdout.

Usage: python scripts/profile_proofs.py [--dps 10] [--cpu] [--small]
(--small: 1 DP, V=8 — the CPU-sized variant).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dps", type=int, default=10)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from drynx_tpu.utils.cache import enable_compilation_cache

        enable_compilation_cache()

    import numpy as np
    import jax.numpy as jnp

    from drynx_tpu.crypto import batching as B
    from drynx_tpu.crypto import curve as C
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.crypto import fp12 as F12
    from drynx_tpu.proofs import range_proof as rp

    out = []

    def stage(name, fn, n=2):
        t0 = time.perf_counter()
        r = fn()
        jax.block_until_ready(r) if r is not None else None
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            r = fn()
            jax.block_until_ready(r) if r is not None else None
            best = min(best, time.perf_counter() - t0)
        rec = {"stage": name, "steady_s": round(best, 4),
               "first_s": round(compile_s, 4)}
        out.append(rec)
        print(json.dumps(rec), flush=True)
        return r

    rng = np.random.default_rng(3)
    U, L = 16, 5
    n_dps = 1 if args.small else args.dps
    V = 8 if args.small else 90
    sigs = [rp.init_range_sig(U, rng) for _ in range(3)]
    _, ca_pub = eg.keygen(rng)
    ca_tbl = eg.pub_table(ca_pub)
    secrets2 = rng.integers(0, U ** L, size=(n_dps, V)).astype(np.int64)
    key = jax.random.PRNGKey(7)
    flat = secrets2.reshape(-1)
    cts, rs = eg.encrypt_ints(jax.random.PRNGKey(8), ca_tbl,
                              jnp.asarray(flat))
    ranges = [(U, L)] * V

    # ---- creation, then its sub-stages on the same shapes
    box = {}

    def _create():
        box["lists"] = rp.create_range_proof_lists_batched(
            key, secrets2, rs.reshape(n_dps, V, 16),
            np.asarray(cts).reshape(n_dps, V, 2, 3, 16), ranges,
            {U: sigs}, ca_tbl.table)

    stage("create_all_dps", _create, n=1)
    lists = box["lists"]

    digits = jnp.asarray(rp.to_base(flat, U, L))
    ns = len(sigs)
    N = flat.shape[0]
    s = eg.random_scalars(jax.random.PRNGKey(1), (N, L))
    t_ = eg.random_scalars(jax.random.PRNGKey(2), (N, L))
    v = eg.random_scalars(jax.random.PRNGKey(4), (ns, N, L))
    A_tab = jnp.asarray(np.stack([sg.A for sg in sigs]))
    gtA = rp.sig_gt_table(sigs)

    stage("c1_g2_blind", lambda: B.g2_scalar_mul(A_tab[:, digits], v))
    gt_sel = gtA[:, digits]
    sv = B.fn_mul_plain(s, v)
    stage("c2_gt_pow_digits", lambda: B.gt_pow(gt_sel, B.fn_neg(sv)))
    stage("c3_gtb_pow", lambda: rp.gt_pow_gtb(t_))
    V_pts = B.g2_scalar_mul(A_tab[:, digits], v)
    a = B.gt_pow(gt_sel, B.fn_neg(sv))
    D = B.fixed_base_mul(eg.BASE_TABLE.table, s[:, 0])
    stage("c4_wire_encode", lambda: jnp.asarray(rp._range_wire_dict(
        np.asarray(cts).reshape(N, 2, 3, 16), D, V_pts, a)["a"][:1]))

    # ---- one DP payload -> bytes (serialization cost; wire cache warm)
    stage("c5_to_bytes", lambda: np.frombuffer(
        lists[0].to_bytes(), dtype=np.uint8))

    # ---- joint RLC verification sub-stages on the concatenated batch
    pubs = {U: [sg.public for sg in sigs]}
    datas = [lst.to_bytes() for lst in lists]
    stage("v_joint_total", lambda: rp.verify_range_proof_payloads_joint(
        datas, ranges, pubs, ca_tbl.table) and None, n=1)

    pb = rp._concat_batches([b for lst in lists for _ia, b in lst.batches])
    stage("v1_prelude_D_chal_member", lambda: rp.rlc_prelude(
        pb, pubs[U], ca_tbl.table) and None)
    # the round-5 soundness gates, isolated (also inside v1's total):
    stage("v1a_membership_gate", lambda: B.gt_membership_ok(pb.a) and None)
    stage("v1b_order_n_gate", lambda: B.gt_order_ok(pb.a) and None)
    pre_ok, r_int, gtb_pow_s = rp.rlc_prelude(pb, pubs[U], ca_tbl.table)
    r = B.int_to_scalar(jnp.asarray(r_int))
    ys = jnp.asarray(np.stack([C.from_ref(p) for p in pubs[U]]))
    c, zphi = pb.challenge, pb.zphi
    cy = B.g1_scalar_mul(ys[:, None, :, :], c[None, :, :])
    nzphiB = B.fixed_base_mul(eg.BASE_TABLE.table, B.fn_neg(zphi))
    g1arg = B.g1_add(cy[:, :, None, :, :], nzphiB[None])
    stage("v2_g1_weight64", lambda: B.g1_scalar_mul64(g1arg, r))
    g1arg_r = B.g1_scalar_mul64(g1arg, r)
    px, py, _ = B.g1_normalize(g1arg_r)
    qx, qy, _ = B.g2_normalize(pb.v_pts)
    stage("v3_miller", lambda: B.miller(px, py, qx, qy))
    m = B.miller(px, py, qx, qy)
    stage("v4_a_pow_r", lambda: B.gt_pow64(F12.conj6(jnp.asarray(pb.a)), r))
    stage("v5_final_exp", lambda: B.final_exp(B.gt_reduce_prod(
        np.asarray(m).reshape(-1, 6, 2, 16))[None]))

    # ---- keyswitch verify at bench shape
    from drynx_tpu.crypto import curve as C
    from drynx_tpu.proofs import keyswitch as ks

    Vv = N
    srv_x = jnp.asarray(np.stack([eg.secret_to_limbs(
        int(rng.integers(1, 1 << 61))) for _ in range(3)]))
    ks_rs = eg.random_scalars(jax.random.PRNGKey(11), (3, Vv))
    K0 = jnp.asarray(np.asarray(cts).reshape(Vv, 2, 3, 16))[:, 0]
    u_pts = B.fixed_base_mul(eg.BASE_TABLE.table, ks_rs)
    q_pt = jnp.asarray(C.from_ref(ca_pub))
    rQ = B.fixed_base_mul(ca_tbl.table, ks_rs)
    xK = B.g1_scalar_mul(K0[None], srv_x[:, None, :])
    w_pts = B.g1_add(rQ, B.g1_neg(xK))
    pr = ks.create_keyswitch_proofs(jax.random.PRNGKey(12), K0, srv_x,
                                    ks_rs, q_pt, ca_tbl.table, u_pts, w_pts)
    stage("ks_verify", lambda: ks.verify_keyswitch_proofs(pr, ca_tbl.table))

    print(json.dumps({"profile": out, "shape": {
        "n_dps": n_dps, "V": V, "l": L, "ns": ns,
        "digits": int(ns * N * L)}}), flush=True)


if __name__ == "__main__":
    main()
