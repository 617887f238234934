"""Chip smoke: the quickest proof that the survey path still starts on a TPU.

    python chip_smoke.py            one chip: device, exec, g1_kernels
    python chip_smoke.py --proofs   one chip: device, proofs
    python chip_smoke.py --chips 4  four chips: device, mesh (and only that)

One process, jax imported once, no children, JAX_PLATFORMS left alone. Every
phase prints one JSON object on its own line; a phase that raises ends the
script non-zero and nothing is caught and skipped. Only a run whose every
phase passed prints the last line,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the device phase returns non-zero at once. There is no CPU,
host-oracle or interpret-mode fallback: that is the point of the script.

Phases:
  device      jax.devices() is a TPU, pallas_ops.available(), not INTERPRET.
  exec        the paper's Pima log-reg deployment at full size through
              LocalCluster.run_survey, proofs off, twice: 3 CN / 10 DP /
              3 VN, 10 x 768 rows x 8 features, K=2, 450 iterations;
              decrypted == clear-text sum bit for bit. What the second
              run still compiles is reported, not asserted: the service
              calls models/logreg.train eagerly, and its GD loop is
              re-traced and re-compiled by every survey (PERF.md).
  g1_kernels  one batch of each G1 Pallas kernel (scalar_mul_flat,
              fixed_base_mul_flat, point_add_flat) against crypto/refimpl
              on seeded inputs, so the default run executes Mosaic code
              called directly, not only inside the fused exec jits.
  proofs      a proofs-on `sum` survey through the same LocalCluster,
              ranges (16, 5), thresholds 1.0, twice: bitmap all BM_TRUE,
              audit chain validates, decrypted == clear sum, and zero
              host-oracle dispatches. Behind --proofs because its cold
              compile bill does not fit the driver's 1200 s (PERF.md).
  mesh        --chips 4 only: plane placement, then sharded joint
              range-proof creation and verification against the same on
              one device (byte-identical transcripts, equal verdicts), with
              the device of every shard's outputs printed.

The compile cache follows the one rule of drynx_tpu/utils/cache.py. Compile
seconds per program go to stderr as they happen, so a run that is cut still
shows where the time went.
"""
import argparse
import json
import sys
import time

SEED = 21


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


class CompileMeter:
    """A view of the program's own tracer (drynx_tpu/utils/timers.py), whose
    jax.monitoring listener sums jax's compile events, so each phase can
    report what it compiled. `requests` counts backend compile requests,
    persistent-cache hits included; a warm in-process run makes none.
    Trace events nest (an inner jit's trace is inside its caller's), so
    they only go to stderr; lower and compile events do not nest."""

    def __init__(self):
        from drynx_tpu.utils import timers

        timers.install_listener()
        self._tracer = timers.PROCESS
        self._tracer.echo_over_s = 1.0  # such jax events go to stderr

    def snapshot(self) -> dict:
        t = self._tracer
        return {"lower_seconds": t["jax/lower"],
                "compile_seconds": t["jax/compile"],
                "requests": t.counter("compile_requests"),
                "hits": t.counter("cache_hits")}


def run_phase(name: str, meter: CompileMeter, cache_dir: str, fn) -> None:
    """Run one phase and print its line. Exceptions propagate."""
    from drynx_tpu.crypto import batching as B
    from drynx_tpu.crypto import pallas_ops as po

    before, host_before = meter.snapshot(), sum(B.HOST_ORACLE_CALLS.values())
    t0 = time.perf_counter()
    extra = fn() or {}
    seconds = time.perf_counter() - t0
    after = meter.snapshot()
    line = {
        "phase": name, "seconds": round(seconds, 3),
        "compile_seconds": round(
            after["compile_seconds"] - before["compile_seconds"], 3),
        "lower_seconds": round(
            after["lower_seconds"] - before["lower_seconds"], 3),
        "compile_requests": after["requests"] - before["requests"],
        "cache_dir": cache_dir,
        "persistent_cache_hits": after["hits"] - before["hits"],
        "host_oracle_calls": sum(B.HOST_ORACLE_CALLS.values()) - host_before,
        "pallas_available": po.available(),
        "interpret": bool(po.INTERPRET),
    }
    line.update(extra)
    emit(line)


# ---------------------------------------------------------------------------
# exec: Pima log-reg through LocalCluster, proofs off
# ---------------------------------------------------------------------------

def phase_exec(meter, n_dps: int = 10, n_records: int = 768, d: int = 8,
               iterations: int = 450, dlog_limit: int = 10000) -> dict:
    import numpy as np

    from drynx_tpu import flagship
    from drynx_tpu.models import logreg as lr
    from drynx_tpu.service.service import LocalCluster

    X, y, params = flagship.pima_shaped_problem(
        num_dps=n_dps, n_records=n_records, d=d, max_iterations=iterations)
    cluster = LocalCluster(n_cns=3, n_dps=n_dps, n_vns=3, seed=SEED,
                           dlog_limit=dlog_limit)
    clear = []
    for i, dp in enumerate(cluster.dps.values()):
        Xi, yi = lr.shard_for_dp(X, y, i, n_dps)
        dp.data = (Xi, yi)
        clear.append(np.asarray(lr.encode_clear(Xi, yi, params)))
    clear_sum = np.stack(clear).sum(axis=0)
    sq = cluster.generate_survey_query("log_reg", proofs=0, lr_params=params)

    runs = []
    for _ in range(2):
        before = meter.snapshot()["requests"]
        t0 = time.perf_counter()
        res = cluster.run_survey(sq)
        runs.append(round(time.perf_counter() - t0, 3))
        compiled = meter.snapshot()["requests"] - before
        np.testing.assert_array_equal(res.decrypted.values, clear_sum)
        w = np.asarray(res.result)
        assert w.shape == (d + 1,) and np.all(np.isfinite(w)), w
    return {"deployment": f"pima-logreg 3cn/{n_dps}dp/3vn "
                          f"{n_records}x{d} k=2 it={iterations} proofs=0",
            "n_ciphertexts": int(clear_sum.shape[0]),
            "decrypted_equals_clear": True,
            "run_seconds": runs, "second_run_compile_requests": compiled}


# ---------------------------------------------------------------------------
# g1_kernels: each G1 Pallas kernel directly, against the int reference
# ---------------------------------------------------------------------------

def phase_g1_kernels(n: int = 128) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from drynx_tpu.crypto import curve as C
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.crypto import field as F
    from drynx_tpu.crypto import pallas_ops as po
    from drynx_tpu.crypto import params, refimpl

    rng = np.random.default_rng(SEED)

    def scalars():
        return [int.from_bytes(rng.bytes(32), "little") % params.N
                for _ in range(n)]

    kp, kq, ks = scalars(), scalars(), scalars()
    ks[0] = 0                                  # edge: zero scalar -> infinity
    p_ref = [refimpl.g1_mul(refimpl.G1, k) for k in kp]
    q_ref = [refimpl.g1_mul(refimpl.G1, k) for k in kq]
    p = jnp.asarray(C.from_ref_batch(p_ref), dtype=jnp.uint32)
    q = jnp.asarray(C.from_ref_batch(q_ref), dtype=jnp.uint32)
    k = jnp.asarray(F.from_int(ks), dtype=jnp.uint32)

    def check(name, out, want):
        got = [C.to_ref(x) for x in np.asarray(out)]
        bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
        assert not bad, f"{name}: {len(bad)}/{n} lanes differ from refimpl"

    check("scalar_mul_flat", po.scalar_mul_flat(p, k),
          [refimpl.g1_mul(pt, s) for pt, s in zip(p_ref, ks)])
    check("fixed_base_mul_flat",
          po.fixed_base_mul_flat(eg.BASE_TABLE.table, k),
          [refimpl.g1_mul(refimpl.G1, s) for s in ks])
    check("point_add_flat", po.point_add_flat(p, q),
          [refimpl.g1_add(a, b) for a, b in zip(p_ref, q_ref)])
    return {"kernels": ["scalar_mul_flat", "fixed_base_mul_flat",
                        "point_add_flat"], "lanes": n,
            "equals_refimpl": True}


# ---------------------------------------------------------------------------
# proofs: a proofs-on sum survey through LocalCluster
# ---------------------------------------------------------------------------

def phase_proofs(meter, n_dps: int = 10, rows: int = 100) -> dict:
    import numpy as np

    from drynx_tpu.crypto import batching as B
    from drynx_tpu.proofs import requests as rq
    from drynx_tpu.service.service import LocalCluster

    cluster = LocalCluster(n_cns=3, n_dps=n_dps, n_vns=3, seed=SEED,
                           dlog_limit=10000)
    rng = np.random.default_rng(SEED)
    total = 0
    for dp in cluster.dps.values():
        dp.data = rng.integers(0, 10, size=(rows,)).astype(np.int64)
        total += int(dp.data.sum())
    sq = cluster.generate_survey_query(
        "sum", query_min=0, query_max=9, proofs=1, ranges=[(16, 5)],
        thresholds=1.0)

    runs = []
    for _ in range(2):
        # the second survey re-sends byte-identical payloads: clear the
        # VNs' verify caches so it verifies again instead of hitting them
        for vn in cluster.vns.vns:
            vn.verify_cache.clear()
        before = meter.snapshot()["requests"]
        host_before = sum(B.HOST_ORACLE_CALLS.values())
        t0 = time.perf_counter()
        res = cluster.run_survey(sq)
        runs.append(round(time.perf_counter() - t0, 3))
        compiled = meter.snapshot()["requests"] - before
        assert res.block is not None, "no audit block committed"
        codes = set(res.block.data.bitmap.values())
        assert codes == {rq.BM_TRUE}, f"dirty bitmap codes: {codes}"
        assert cluster.vns.root.chain.validate(), "audit chain invalid"
        np.testing.assert_array_equal(res.decrypted.values, [total])
        host = sum(B.HOST_ORACLE_CALLS.values()) - host_before
        assert host == 0, f"host oracle ran: {dict(B.HOST_ORACLE_CALLS)}"
    assert compiled == 0, f"second proofs run compiled {compiled} programs"
    return {"deployment": f"sum 3cn/{n_dps}dp/3vn ranges=(16,5) "
                          f"thresholds=1.0 proofs=1",
            "bitmap_all_true": True, "audit_chain_valid": True,
            "decrypted_equals_clear": True, "n_proofs": len(
                res.block.data.bitmap),
            "run_seconds": runs, "second_run_compile_requests": compiled}


# ---------------------------------------------------------------------------
# mesh: the proof plane on four devices against one device
# ---------------------------------------------------------------------------

def phase_mesh(meter, n_devices: int = 4) -> dict:
    """The shapes of tests/test_proof_mesh.py. `plane.gather` is wrapped
    here, in the script, to see where each shard's outputs live before
    they are brought back to the lead device. Every step prints its own
    line with the compile requests it made: on TPU each device compiles
    its own copy of a program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from drynx_tpu.crypto import batching as B
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import proof_mesh as pm
    from drynx_tpu.parallel import proof_plane as plane
    from drynx_tpu.proofs import range_proof as rp

    assert plane.n_shards() == n_devices, plane.n_shards()
    placement_expected = plane.placement_on()
    seen: list = []
    real_gather = plane.gather
    last = meter.snapshot()

    def recording_gather(tree):
        devs = {d.id for leaf in jax.tree_util.tree_leaves(tree)
                for d in leaf.devices()}
        seen.append(sorted(devs))
        return real_gather(tree)

    def shard_devices(what: str) -> list:
        """Devices of the shards dispatched since the last call; on a
        mesh with placement on they must be all distinct."""
        nonlocal last
        devs, seen[:] = list(seen), []
        now = meter.snapshot()
        emit({"phase": "mesh", "step": what, "shard_output_devices": devs,
              "compile_requests": now["requests"] - last["requests"],
              "compile_seconds": round(
                  now["compile_seconds"] - last["compile_seconds"], 3)})
        last = now
        if placement_expected:
            assert all(len(d) == 1 for d in devs), devs
            assert len({d[0] for d in devs}) == len(devs), \
                f"{what}: shards share a device: {devs}"
        return devs

    plane.gather = recording_gather
    try:
        # placement first, with the cheapest Mosaic program of the proof
        # path (fixed_base_mul, seconds to build): do put_shard /
        # dispatch_shards / gather spread the shards over the devices, and
        # does a kernel run on each of them?
        base = eg.BASE_TABLE.table
        k = eg.random_scalars(jax.random.PRNGKey(SEED), (n_devices * 8,))
        slices = plane.shard_slices(k.shape[0], n_devices)
        parts = plane.dispatch_shards(
            "SmokeShard", lambda i, s: B.fixed_base_mul(base, s), slices,
            prefetch=lambda i, a, b: (plane.put_shard(k[a:b], i,
                                                      donate=True),))
        placed = shard_devices("placement")
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p) for p in parts]),
            np.asarray(B.fixed_base_mul(base, k)))

        rng = np.random.default_rng(SEED)
        u, l, ns = 4, 2, 2
        sigs = [rp.init_range_sig(u, rng) for _ in range(ns)]
        _, ca_pub = eg.keygen(rng)
        ca_tbl = eg.pub_table(ca_pub)
        values = np.asarray([3, 15, 0, 7], dtype=np.int64)
        cts, rs = eg.encrypt_ints(jax.random.PRNGKey(SEED), ca_tbl, values)
        pubs = [s.public for s in sigs]

        def create(shard):
            return rp.create_range_proofs(
                jax.random.PRNGKey(SEED + 1), values, rs, cts, sigs, u, l,
                ca_tbl.table, shard=shard)

        single = create(False)
        assert not seen, "single-device creation went through the plane"
        sharded = create(True)
        created = shard_devices("create")
        assert sharded.to_bytes() == single.to_bytes(), \
            "sharded creation diverged from the single-device transcript"

        pre_ok, r_int, gtb_pow_s = rp.rlc_prelude(
            single, pubs, ca_tbl.table, rng=np.random.default_rng(SEED))
        assert pre_ok
        t_single = np.asarray(rp.rlc_total_single(single, pubs, r_int,
                                                  gtb_pow_s))
        t_shards = np.asarray(pm.rlc_total_shards(single, pubs, r_int,
                                                  gtb_pow_s))
        verified = shard_devices("verify")
        assert np.array_equal(t_single, t_shards), \
            "sharded RLC total != single-device total"
        v_shard = pm.rlc_verify_sharded(
            single, pubs, ca_tbl.table, rng=np.random.default_rng(SEED + 2))
        v_single = rp.verify_range_proofs_batch(
            single, pubs, ca_tbl.table, rng=np.random.default_rng(SEED + 2))
        assert v_shard and v_single, (v_shard, v_single)
    finally:
        plane.gather = real_gather
    return {"n_shards": n_devices, "placement_on": placement_expected,
            "placement_devices": placed, "create_devices": created,
            "verify_devices": verified, "transcripts_identical": True,
            "totals_identical": True, "verdicts": [v_shard, v_single]}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--proofs", action="store_true",
                    help="run the proofs-on survey instead of exec")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the proof-plane mesh phase")
    args = ap.parse_args(argv)

    import jax

    from drynx_tpu.crypto import pallas_ops as po
    from drynx_tpu.utils.cache import enable_compilation_cache

    t0 = time.perf_counter()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    line = {"phase": "device", "seconds": round(time.perf_counter() - t0, 3),
            "device": device, "interpret": bool(po.INTERPRET)}
    if device["platform"] != "tpu":
        emit(dict(line, error="no TPU: jax.devices() is "
                              f"{device['platform']}"))
        return 2
    if device["count"] != args.chips:
        emit(dict(line, error=f"--chips {args.chips} but jax sees "
                              f"{device['count']} devices"))
        return 2
    if po.INTERPRET or not po.available():
        emit(dict(line, error="Pallas kernels would not run as Mosaic "
                              "code here", pallas_available=po.available()))
        return 2
    cache_dir = enable_compilation_cache()
    emit(dict(line, pallas_available=True, cache_dir=cache_dir))

    meter = CompileMeter()
    if args.chips == 4:
        run_phase("mesh", meter, cache_dir, lambda: phase_mesh(meter))
    elif args.proofs:
        run_phase("proofs", meter, cache_dir, lambda: phase_proofs(meter))
    else:
        run_phase("exec", meter, cache_dir, lambda: phase_exec(meter))
        run_phase("g1_kernels", meter, cache_dir, phase_g1_kernels)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
