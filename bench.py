"""Headline benchmark: encrypted logistic-regression training, Pima-shaped
(10 DPs x 768 distinct records each, 8 features, K=2, 450 GD iterations),
WITH the verification pipeline on: DP encode+encrypt + range-proof creation
-> collective aggregation (+ proof) -> key switch (+ proofs) -> VN
verification of every proof -> audit-block commit -> querier decrypt -> GD.

Baselines (BASELINE.md, reference TIFS/logRegV2.py:9-14, Go/CPU):
  proofs ON  total: 12.2 s   (exec 1.2 + proof overhead 10.9 + decode 0.12)
  exec-only  total: ~1.32 s  (exec + decode, no proofs)

SUPERVISOR architecture (round-5 VERDICT weak #1): five rounds of bench
attempts died to segfaults/timeouts INSIDE the measured process — no
amount of in-process "un-killable one-JSON-line" armor survives a SIGSEGV
in a kernel dispatch. So the process that prints the record is no longer
the process that crashes:

  * the PARENT (this script, no args) never imports jax. It probes the
    backend, probes persistent-cache deserialization (both in supervised
    children), runs the measurement in a CHILD process, and emits EXACTLY
    ONE labeled JSON line on stdout for every child outcome — clean exit,
    nonzero rc, segfault, timeout (the same pattern as
    __graft_entry__.py dryrun children).
  * the CHILD (`--measure-child`) does all JAX work and writes a
    PROGRESSIVE record file (--record-path) at each stage — starting ->
    cluster_built -> warmup_done -> complete/failed — carrying phase
    timers, compile_cache_* attribution and per-shard proof-plane timers,
    so even a segfaulted run is attributable from JSON alone.
  * whether the persistent compilation cache round-trips on this backend
    is MEASURED, not assumed: `--cache-probe-child` compiles into the cache
    (drynx_tpu/utils/cache.py: JAX_COMPILATION_CACHE_DIR if set, else
    <checkout>/.jax_cache), a second probe child must deserialize out of
    it; only an "ok" verdict lets the measured child turn the cache on,
    and the verdict is recorded in the headline JSON either way.

The parent exits 0 only when the measured child filed a complete proofs-on
record; every other outcome still prints its one labeled JSON line and
exits non-zero. There is no fallback metric: a proofs-on run that fails is
a failed bench, not an exec-only number.
"""
import faulthandler
import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_PROOFS_S = 12.2
BASELINE_EXEC_S = 1.32
RANGES = (16, 5)     # reference simulation preset 18 (drynx_simul.go case 18)

# --no-verify-cache: the UNDEDUPED control run (round-4 VERDICT task 10).
# The default headline lets co-located VNs share one VerifyCache — identical
# payloads verify once per process, matching the reference's
# parallel-machines accounting (each of its VNs verifies on its own box
# simultaneously; dedup factor: 9 keyswitch verifies -> 1, 3 joint-range ->
# 1). This flag DISABLES the cache (VerifyCache maxsize=0) so every
# delivery recomputes — all 9 keyswitch verifies run — and the true
# single-chip SERIAL cost of all verifications lands beside the headline.
NO_DEDUP = "--no-verify-cache" in sys.argv

_t0 = time.time()
_JSON_DONE = False
_CURRENT_CHILD = None       # Popen of the running child (signal forwarding)
_RECORD_PATH = None         # child mode: where progressive records go

CHILD_TIMEOUT_S = float(os.environ.get("DRYNX_BENCH_CHILD_TIMEOUT_S", 3300))
PROBE_TIMEOUT_S = float(os.environ.get("DRYNX_BENCH_PROBE_TIMEOUT_S", 600))
NO_CACHE_FLAG = "--no-persistent-cache"   # parent -> measured child


def log(msg):
    print(f"[{time.time() - _t0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def emit(obj) -> None:
    """The ONE-JSON-line contract: first call wins, later calls are logs."""
    global _JSON_DONE
    if _JSON_DONE:
        log(f"suppressed extra JSON (contract is one line): {obj}")
        return
    _JSON_DONE = True
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# Supervisor plumbing (parent side — no jax anywhere on these paths)
# ---------------------------------------------------------------------------

def _arm_supervisor():
    """Parent signal/faulthandler armor: a driver SIGTERM mid-run still
    produces the labeled JSON (and the child is killed, not orphaned)."""
    faulthandler.register(signal.SIGUSR1, file=sys.stderr)

    def _signal_exit(signum, frame):
        # os.write: async-signal-safe; print() inside a handler raises
        # 'reentrant call' if the signal lands mid-print on the main thread
        global _JSON_DONE
        if not _JSON_DONE:
            _JSON_DONE = True
            line = json.dumps({
                "metric": "bench_interrupted_before_headline",
                "value": round(time.time() - _t0, 1), "unit": "s_elapsed",
                "vs_baseline": 0.0, "signal": int(signum)}) + "\n"
            os.write(1, line.encode())
        child = _CURRENT_CHILD
        if child is not None:
            try:
                child.kill()
            except OSError:
                pass
        os._exit(1)

    signal.signal(signal.SIGTERM, _signal_exit)
    signal.signal(signal.SIGINT, _signal_exit)


def supervise_child(cmd, timeout_s, env=None):
    """Run cmd to completion under this supervisor.

    Returns (outcome, rc, elapsed_s, stdout_text) with outcome one of
    "ok" | "rc:<n>" | "signal:<NAME>" | "timeout". stderr is inherited
    (live logs stay visible); stdout is captured so a chatty child can
    never violate the parent's one-JSON-line contract."""
    global _CURRENT_CHILD
    t0 = time.time()
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE, text=True)
    _CURRENT_CHILD = proc
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        return "timeout", None, time.time() - t0, out or ""
    finally:
        _CURRENT_CHILD = None
    rc = proc.returncode
    if rc == 0:
        outcome = "ok"
    elif rc < 0:
        try:
            outcome = "signal:" + signal.Signals(-rc).name
        except ValueError:
            outcome = f"signal:{-rc}"
    else:
        outcome = f"rc:{rc}"
    return outcome, rc, time.time() - t0, out or ""


def read_record(path):
    """Best-effort read of the child's progressive record file."""
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return {}


def cache_verdict(first, second):
    """Map the two cache-probe child outcomes to a verdict string.

    first/second: (outcome, rc) from supervise_child; second is None when
    the first probe already failed. Probe children exit 0 when the
    persistent-cache listener saw a HIT, 7 on no hit (expected for the
    first, compile-and-serialize, run). Only "ok" enables the cache for
    the measured child."""
    f_out, f_rc = first
    if f_out == "timeout":
        return "write_timeout"
    if f_out.startswith("signal:"):
        return "write_crash"
    if f_rc not in (0, 7):
        return "write_failed"
    if second is None:
        return "write_failed"
    s_out, s_rc = second
    if s_out == "timeout":
        return "deserialize_timeout"
    if s_out.startswith("signal:"):
        return "deserialize_crash"
    if s_rc == 0:
        return "ok"
    if s_rc == 7:
        return "no_hit"
    return "deserialize_error"


def probe_persistent_cache():
    """Measure, in supervised children, whether the persistent XLA cache
    round-trips on this backend (write then deserialize). The probe
    children use the cache the measured child would use (one rule:
    drynx_tpu/utils/cache.py), so a warm cache makes the first pass a hit
    already."""
    cmd = [sys.executable, os.path.abspath(__file__), "--cache-probe-child"]

    first = supervise_child(cmd, PROBE_TIMEOUT_S)
    log(f"cache probe write pass: outcome={first[0]} in {first[2]:.0f}s")
    second = None
    if first[0] in ("ok", "rc:7"):
        second = supervise_child(cmd, PROBE_TIMEOUT_S)
        log(f"cache probe read pass: outcome={second[0]} in {second[2]:.0f}s")
    verdict = cache_verdict((first[0], first[1]),
                            None if second is None else (second[0], second[1]))
    log(f"persistent-cache verdict: {verdict}")
    return verdict


def probe_backend(max_tries: int = 2, attempt_timeout: float = 300.0,
                  total_budget: float = 620.0) -> bool:
    """Pre-flight the JAX backend in a SUBPROCESS with bounded retry: the
    r03 record died on an init-time 'UNAVAILABLE' raised by the first
    in-process dispatch — before any try/except could save the JSON.
    Probing out-of-process keeps a poisoned backend-init state out of this
    process and lets a transiently-unavailable chip recover.

    The TOTAL probe wall time is hard-capped (round-4 VERDICT weak #1: the
    old 4x600s budget outlived the driver's ~30 min SIGTERM and recorded
    `bench_interrupted_before_headline` instead of the honest
    `bench_failed_tpu_unavailable`). 2x300s + one short backoff stays well
    inside any plausible driver window, and per-attempt elapsed is logged
    so a 5-min-hanging jax.devices() is distinguishable from a fast
    refusal."""
    # One process per chip: a chip belongs to one process at a time. This
    # parent never imports jax, so it never holds the chip, and its three
    # children (this probe, the cache probe's two passes, the measured
    # child) run strictly in sequence, each exiting before the next
    # starts. Keep both properties.
    probe_t0 = time.time()
    for i in range(max_tries):
        left = total_budget - (time.time() - probe_t0)
        if left <= 5.0:
            log(f"probe budget exhausted ({total_budget:.0f}s total cap)")
            break
        t0 = time.time()
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 "import jax; d=jax.devices(); print(d[0].platform)"],
                capture_output=True, text=True,
                timeout=min(attempt_timeout, left))
            dt = time.time() - t0
            if r.returncode == 0:
                log(f"backend probe ok in {dt:.0f}s: {r.stdout.strip()}")
                return True
            log(f"backend probe attempt {i + 1}/{max_tries} rc={r.returncode}"
                f" after {dt:.0f}s: {r.stderr.strip()[-400:]}")
        except subprocess.TimeoutExpired:
            log(f"backend probe attempt {i + 1}/{max_tries} timed out "
                f"after {time.time() - t0:.0f}s")
        if i + 1 < max_tries:   # no pointless backoff after the last try
            time.sleep(10.0)
    return False


def supervisor_result(outcome, rc, elapsed_s, record, cache_probe):
    """Build the parent's ONE JSON object from a measured-child outcome and
    its last progressive record (pure — unit-tested with stub children).

    A child that completed writes stage="complete" with the metric fields;
    anything else becomes a labeled failure metric carrying the last stage
    reached plus whatever timers/attribution the record accumulated."""
    sup = {"child_outcome": outcome,
           "child_rc": rc,
           "child_elapsed_s": round(elapsed_s, 1),
           "persistent_cache_probe": cache_probe}
    rec = dict(record or {})
    stage = rec.pop("stage", None)
    if outcome == "ok" and stage == "complete" and "metric" in rec:
        rec.update(sup)
        return rec
    if outcome == "ok":
        metric = "bench_child_exited_without_headline"
    elif outcome == "timeout":
        metric = "bench_child_timeout"
    elif outcome.startswith("signal:"):
        metric = "bench_child_killed_" + outcome.split(":", 1)[1].lower()
    else:
        metric = "bench_child_failed_" + outcome.replace(":", "")
    rec.pop("metric", None)
    rec.pop("value", None)
    rec.pop("unit", None)
    rec.pop("vs_baseline", None)
    return {"metric": metric, "value": round(elapsed_s, 1),
            "unit": "s_elapsed", "vs_baseline": 0.0,
            "last_stage": stage or "none", **rec, **sup}


def result_exit_code(result) -> int:
    """The parent's exit code for a supervisor_result: 0 only for a complete
    measured record (every failure label carries `last_stage`)."""
    return 1 if "last_stage" in result else 0


def main_supervisor() -> int:
    """Parent: probe backend + cache, supervise the measured child, emit.
    Returns the exit code: 0 iff the child filed a complete record."""
    _arm_supervisor()
    if not probe_backend():
        emit({"metric": "bench_failed_tpu_unavailable",
              "value": 0.0, "unit": "s", "vs_baseline": 0.0})
        return 1

    cache_probe = probe_persistent_cache()

    here = os.path.dirname(os.path.abspath(__file__))
    record_path = os.path.join(here, ".bench_record.json")
    try:
        os.remove(record_path)
    except OSError:
        pass
    env = dict(os.environ)
    cmd = [sys.executable, os.path.abspath(__file__), "--measure-child",
           "--record-path", record_path]
    if cache_probe != "ok":
        # the measured child must NOT enable what the probe says crashes
        cmd.append(NO_CACHE_FLAG)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if NO_DEDUP:
        cmd.append("--no-verify-cache")

    log(f"starting measured child (timeout {CHILD_TIMEOUT_S:.0f}s, "
        f"cache={'on' if cache_probe == 'ok' else 'off'})")
    outcome, rc, elapsed, _out = supervise_child(cmd, CHILD_TIMEOUT_S,
                                                 env=env)
    log(f"measured child done: outcome={outcome} in {elapsed:.0f}s")
    result = supervisor_result(outcome, rc, elapsed,
                               read_record(record_path), cache_probe)
    emit(result)
    return result_exit_code(result)


# ---------------------------------------------------------------------------
# Child side (all jax work lives below; parent never imports these paths)
# ---------------------------------------------------------------------------

def write_record(obj) -> None:
    """Progressive child record: atomic replace so the parent never reads a
    torn write, even if this process dies mid-dump."""
    if _RECORD_PATH is None:
        return
    obj = dict(obj)
    obj.setdefault("elapsed_s", round(time.time() - _t0, 1))
    tmp = _RECORD_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, _RECORD_PATH)


def _arm_child():
    """Child armor: stack dumps on demand/stall + a SIGTERM record update
    (the parent still emits the JSON line — the child only files evidence).
    """
    faulthandler.register(signal.SIGUSR1, file=sys.stderr)
    faulthandler.dump_traceback_later(900, repeat=True, file=sys.stderr)

    def _sig(signum, frame):
        write_record({"stage": "interrupted", "signal": int(signum)})
        faulthandler.dump_traceback(file=sys.stderr)
        os._exit(1)

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)


def _cache_probe_child() -> int:
    """Compile two representative programs with the persistent cache on
    (utils/cache.enable_compilation_cache). Exit 0 iff the
    cache listener saw a deserialization HIT (second run), 7 on a clean
    miss (first run), nonzero on any error; a segfault surfaces as the
    child's signal rc. The probed classes: one bucketed crypto op at the
    bench bucket and one fused exec jit — the two program families whose
    CPU executables got large enough to crash jaxlib's deserializer."""
    import jax

    # the probe must serialize regardless of compile speed
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import jax.numpy as jnp

    from drynx_tpu import compilecache as cc
    from drynx_tpu.crypto import batching as B
    from drynx_tpu.utils.cache import enable_compilation_cache

    log(f"cache probe child: cache dir {enable_compilation_cache()}")

    cc.install_cache_listener()
    x = jnp.zeros((2048, 16), dtype=jnp.uint32)
    jax.block_until_ready(B.fn_add(x, x))

    from drynx_tpu.service import service as svc

    a = jnp.zeros((10, 9, 2, 3, 16), dtype=jnp.uint32)
    jax.block_until_ready(svc._fused_agg(a))

    hits = cc.STATS.listener_hits
    log(f"cache probe child: listener hits={hits}")
    return 0 if hits > 0 else 7


def bench_exec():
    """Exec-only path: the fully-jitted single-chip pipeline."""
    import jax
    import numpy as np

    from drynx_tpu import flagship
    from drynx_tpu.crypto import elgamal as eg

    num_dps, n_servers = 10, 3
    X, y, params = flagship.pima_shaped_problem(
        num_dps=num_dps, n_records=768, d=8, max_iterations=450)
    setup = flagship.SurveySetup.create(n_servers=n_servers, dlog_limit=10000)
    fn = jax.jit(flagship.build_pipeline(setup, params))

    stats, enc_rs, _, k2 = flagship.make_inputs(X, y, params, num_dps)
    V = stats.shape[1]
    ks_rs = eg.random_scalars(k2, (n_servers, V))

    # warmup / compile
    w, dec, found = fn(stats, enc_rs, ks_rs)
    jax.block_until_ready(w)
    assert bool(np.all(np.asarray(found))), "discrete-log lookup failed"
    clear = np.asarray(stats).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(dec), clear)

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        w, dec, found = fn(stats, enc_rs, ks_rs)
        jax.block_until_ready(w)
        best = min(best, time.perf_counter() - t0)
    return best


def _proofs_on_cluster():
    import numpy as np

    from drynx_tpu import flagship
    from drynx_tpu.models import logreg as lr
    from drynx_tpu.service.service import LocalCluster

    num_dps = 10
    X, y, params = flagship.pima_shaped_problem(
        num_dps=num_dps, n_records=768, d=8, max_iterations=450)
    cluster = LocalCluster(n_cns=3, n_dps=num_dps, n_vns=3, seed=4,
                           dlog_limit=10000,
                           share_verify_cache=not NO_DEDUP)
    clear_stats = []
    for i, dp in enumerate(cluster.dps.values()):
        Xi, yi = lr.shard_for_dp(X, y, i, num_dps)
        dp.data = (Xi, yi)
        clear_stats.append(np.asarray(lr.encode_clear(Xi, yi, params)))
    clear_sum = np.stack(clear_stats).sum(axis=0)

    V = params.num_coeffs()
    sq = cluster.generate_survey_query(
        "log_reg", proofs=1, lr_params=params,
        ranges=[RANGES] * V, thresholds=1.0)
    return cluster, sq, clear_sum


def _attribution(cc, res=None):
    """The shared record payload: AOT/compile-cache accounting, survey
    phase timers and per-shard proof-plane timers — everything needed to
    attribute a slow (or dead) run from JSON alone."""
    from drynx_tpu.parallel import proof_plane as plane

    out = dict(cc.STATS.headline())
    out["proof_plane_shards"] = plane.n_shards()
    out["shard_timers"] = plane.timers_snapshot()
    if res is not None:
        out["phase_timers"] = {k: round(v, 4)
                               for k, v in res.timers.items()}
    return out


def main_child():
    """Proofs-on; file the headline record after the FIRST timed run.

    The parent emits the JSON — this process only writes the progressive
    record. A proofs-on failure files a 'failed' record and exits non-zero
    (the parent labels the emitted line from child rc + record); there is
    no other metric to fall back to."""
    _arm_child()
    write_record({"stage": "starting"})
    try:
        import numpy as np

        from drynx_tpu import compilecache as cc
        from drynx_tpu.proofs import requests as rq
        from drynx_tpu.utils.timers import PhaseTimers

        cc.CompileStats.echo = True  # per-program AOT rows to stderr live
        cc.install_cache_listener()  # count persistent-cache hits

        # persistent cache: on unless the parent's probe verdict said the
        # round-trip fails on this backend
        if NO_CACHE_FLAG in sys.argv:
            log("persistent cache: off (probe verdict)")
        else:
            from drynx_tpu.utils.cache import enable_compilation_cache

            log(f"persistent cache dir: {enable_compilation_cache()}")

        log("building proofs-on cluster (3 CN / 10 DP / 3 VN, "
            "thresholds=1.0)")
        cluster, sq, clear_sum = _proofs_on_cluster()
        write_record({"stage": "cluster_built", **_attribution(cc)})

        def run():
            # Successive surveys over the same seed re-send byte-identical
            # payloads, so a timed run after warmup would verify NOTHING —
            # every verdict would be a VerifyCache hit from the previous
            # run and the headline would silently exclude verification
            # compute. Clearing the caches keeps the WITHIN-run cross-VN
            # dedup (the disclosed vn_verify_dedup factor) while forcing
            # every proof type to actually verify in the timed window.
            if cluster.vns is not None:
                for vn in cluster.vns.vns:
                    vn.verify_cache.clear()
            t0 = time.perf_counter()
            res = cluster.run_survey(sq)
            dt = time.perf_counter() - t0
            assert res.block is not None, "no audit block committed"
            codes = set(res.block.data.bitmap.values())
            assert codes == {rq.BM_TRUE}, f"dirty bitmap codes: {codes}"
            np.testing.assert_array_equal(res.decrypted.values, clear_sum)
            assert np.all(np.isfinite(res.result))
            return dt, res

        def timers(res):
            return ", ".join(f"{k}={v:.3f}s" for k, v in res.timers.items())

        log("proofs-on warmup (compile) run starting")
        dt, res = run()
        log(f"proofs-on warmup done in {dt:.1f}s; timers: {timers(res)}")
        write_record({"stage": "warmup_done", "warmup_s": round(dt, 2),
                      **_attribution(cc, res)})
        dt, res = run()
        log(f"proofs-on timed run 1: {dt:.4f}s; timers: {timers(res)}")
    except Exception as e:  # keep the bench record honest but non-empty
        import traceback

        log("proofs-on bench FAILED: " + traceback.format_exc(limit=8))
        write_record({"stage": "failed", "error": repr(e)[:400]})
        return 1

    # The deliverable: file NOW, before any bonus measurement can die.
    write_record({
        "stage": "complete",
        "metric": "encrypted_logreg_pima_10dp_proofs_on_total_seconds"
                  + ("_undeduped" if NO_DEDUP else ""),
        "value": round(dt, 4),
        "unit": "s",
        "vs_baseline": round(BASELINE_PROOFS_S / dt, 2),
        # co-located VNs share one VerifyCache unless --no-verify-cache:
        # 9 keyswitch verifies -> 1 compute, 3 joint-range -> 1 (the
        # reference's VNs do this same work in PARALLEL on separate boxes)
        "vn_verify_dedup": not NO_DEDUP,
        # per-VN verify caches are cleared before the timed window (see
        # run() above), so verification compute is inside the measurement
        "verify_cache_cleared": True,
        **_attribution(cc, res),
    })
    log(f"headline recorded: proofs-on {dt:.4f}s = "
        f"{BASELINE_PROOFS_S / dt:.1f}x vs the 12.2s proofs-on baseline")

    # Bonus diagnostics (stderr only, best-effort).
    try:
        dt2, res = run()
        log(f"proofs-on timed run 2: {dt2:.4f}s; timers: {timers(res)}")
        exec_best = bench_exec()
        log(f"exec-only best {exec_best:.4f}s  "
            f"(vs {BASELINE_EXEC_S}s exec baseline: "
            f"{BASELINE_EXEC_S / exec_best:.1f}x)")
    except Exception as e:
        log(f"bonus diagnostics failed (headline already out): {e!r}")
    return 0


if __name__ == "__main__":
    if "--cache-probe-child" in sys.argv:
        sys.exit(_cache_probe_child())
    elif "--measure-child" in sys.argv:
        if "--record-path" in sys.argv:
            _RECORD_PATH = sys.argv[sys.argv.index("--record-path") + 1]
        try:
            rc = main_child()
        except BaseException as e:  # file evidence; parent labels the line
            if isinstance(e, SystemExit):
                raise
            import traceback

            log("bench child top-level failure: "
                + traceback.format_exc(limit=8))
            write_record({"stage": "failed", "error": repr(e)[:400]})
            rc = 1
        sys.exit(rc)
    else:
        rc = 1
        try:
            rc = main_supervisor()
        except BaseException as e:  # truly last-resort: record must parse
            if not isinstance(e, SystemExit):
                import traceback

                log("bench supervisor failure: "
                    + traceback.format_exc(limit=8))
                emit({"metric": "bench_failed_toplevel", "value": 0.0,
                      "unit": "s", "vs_baseline": 0.0,
                      "error": repr(e)[:400]})
        finally:
            if not _JSON_DONE:
                emit({"metric": "bench_exited_without_headline",
                      "value": 0.0, "unit": "s", "vs_baseline": 0.0})
            sys.exit(rc)
