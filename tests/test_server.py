"""The standing survey server (drynx_tpu/server): admission control,
cross-survey batched verification, the encode/verify pipeline.

Quick tier: registry contracts for the cross-survey (n_queue) program
set, admission triage over a stub cluster, scheduler mechanics
(grouping, bounded depth, neighbor isolation, the verify worker) with
the compile driver monkeypatched out, transcript determinism, and the
span-intersection overlap metric — no real surveys, no compiles.

Slow tier: one proofs-on end-to-end run asserting the headline
properties (batched-vs-serial byte-identical transcripts, compile-lane
admission, measured pipeline overlap, zero off-MainThread tracing) and
one FaultPlan soak (a killed DP degrades membership without poisoning
the queue's other surveys)."""
import dataclasses
import threading
import types

import numpy as np
import pytest

from drynx_tpu import compilecache as cc
from drynx_tpu.server import (AdmissionController, Overloaded, QueueFull,
                              QuotaExceeded, SurveyServer,
                              pipeline_overlap, survey_transcript,
                              transcript_digest)
from drynx_tpu.utils.timers import PhaseTimers


# -- registry: the cross-survey program set ----------------------------------

def test_registry_queue_program_set():
    """Profile.n_queue > 1 must only ever ADD programs — the concat
    buckets the batched verify dispatches — on the CrossSurvey phases,
    so admission folding n_queue into the profile certifies batching
    without losing single-survey AOT coverage."""
    base = cc.BENCH
    queued = cc.build_registry(
        cc.Profile(n_cns=base.n_cns, n_dps=base.n_dps,
                   n_values=base.n_values, u=base.u, l=base.l,
                   dlog_limit=base.dlog_limit, n_queue=3))
    flat = cc.build_registry(base)
    flat_names = {s.name for s in flat}
    queued_names = {s.name for s in queued}
    assert flat_names <= queued_names
    extra = [s for s in queued if s.name not in flat_names]
    assert extra, "n_queue=3 must add cross-survey programs"
    phases = {s.phase for s in extra}
    assert phases <= {"CrossSurveyVerify", "CrossSurveyVerifyShard"}
    assert "CrossSurveyVerify" in phases
    # the worker-dispatched scalar family is covered at the concat width
    ops = {s.op for s in extra}
    assert {"int_to_scalar", "to_mont_p"} <= ops


def test_registry_n_queue_one_is_identity():
    base = cc.BENCH
    one = cc.build_registry(dataclasses.replace(base, n_queue=1))
    assert {s.name for s in one} == {s.name for s in cc.build_registry(base)}


def test_worker_ops_are_registry_owned_and_covered():
    """The verify-worker op set lives in the registry (cc.WORKER_OPS), so
    the compile lane's `only` filter and the pool's warm-coverage story
    stay in lockstep: every worker op must have a device-family spec in
    any profile, and worker_specs must select exactly those."""
    specs = cc.worker_specs(cc.BENCH)
    assert specs, "worker op set must be covered by the registry"
    assert {s.op for s in specs} == set(cc.WORKER_OPS)
    assert all(s.family == "device" for s in specs)
    names = {s.name for s in cc.build_registry(cc.BENCH)}
    assert {s.name for s in specs} <= names


# -- stub plumbing -----------------------------------------------------------

class _FakeVNs:
    def __init__(self):
        self.flushed: list = []

    def flush_cross_survey(self, sids):
        self.flushed.append(list(sids))
        return list(sids)


class _FakeCluster:
    """Just enough surface for AdmissionController + SurveyServer."""

    def __init__(self):
        self.cns = ["cn0", "cn1"]
        self.dp_idents = ["dp0", "dp1"]
        self.vns = _FakeVNs()
        self.dlog = types.SimpleNamespace(limit=4000)
        self._proof_device_lock = threading.Lock()
        self.executed: list = []
        self.exec_kwargs: list = []
        self.finalized: list = []
        self.fail_encode: set = set()
        self.fail_encode_once: set = set()

    def _ranges_per_value(self, q):
        return [(4, 2)]

    def execute_survey(self, sq, seed=0, hold_range=False,
                       tenant="default", responders=None):
        self.executed.append((sq.survey_id, hold_range,
                              threading.current_thread().name))
        self.exec_kwargs.append((sq.survey_id, tenant, responders))
        if sq.survey_id in self.fail_encode:
            if sq.survey_id in self.fail_encode_once:
                self.fail_encode.discard(sq.survey_id)
                self.fail_encode_once.discard(sq.survey_id)
            raise RuntimeError(f"boom {sq.survey_id}")
        return types.SimpleNamespace(
            sq=sq, hold_range=hold_range, tenant=tenant,
            survey=types.SimpleNamespace(proof_threads=[]))

    def finalize_survey(self, pending):
        sid = pending.sq.survey_id
        self.finalized.append((sid, threading.current_thread().name))
        return f"result-{sid}"


def _sq(sid, proofs=1):
    return types.SimpleNamespace(survey_id=sid,
                                 query=types.SimpleNamespace(proofs=proofs))


@pytest.fixture
def no_compile(monkeypatch):
    """Replace the AOT driver with a recorder: scheduler tests exercise
    lane mechanics, not XLA."""
    calls = []

    def fake_precompile(profile, mode="execute", stats=None, log=None,
                        only=None):
        calls.append((profile, mode, only))
        return {}

    monkeypatch.setattr(cc, "precompile", fake_precompile)
    return calls


# -- admission ---------------------------------------------------------------

def test_admission_proofs_off_is_fast_lane_with_no_profile():
    adm = AdmissionController(_FakeCluster(), n_queue=2)
    a = adm.triage(_sq("s0", proofs=0))
    assert (a.lane, a.profile, a.missing) == ("fast", None, ())


def test_admission_cold_shape_goes_to_compile_lane_then_warms():
    adm = AdmissionController(_FakeCluster(), n_queue=2)
    a = adm.triage(_sq("s0"))
    assert a.lane == "compile" and a.missing
    assert a.profile.n_queue == 2  # batching is certified by admission
    adm.note_warmed(a.profile)
    b = adm.triage(_sq("s1"))
    assert b.lane == "fast" and not b.missing


def test_admission_warmth_is_keyed_by_program_name_not_profile():
    # warming the n_queue=2 profile covers the n_queue=1 subset shape
    cl = _FakeCluster()
    wide = AdmissionController(cl, n_queue=2)
    wide.note_warmed(wide.profile_for(_sq("s0")))
    narrow = AdmissionController(cl, n_queue=1)
    narrow._warm = wide._warm  # same process-wide set in the server
    assert narrow.triage(_sq("s1")).lane == "fast"


# -- scheduler mechanics -----------------------------------------------------

def _warm_server(cl, **kw):
    srv = SurveyServer(cl, **kw)
    srv.admission.note_warmed(srv.admission.profile_for(_sq("_warm")))
    return srv


def test_submit_rejects_past_max_depth_with_typed_error():
    srv = _warm_server(_FakeCluster(), max_depth=2, pipeline=False)
    srv.submit(_sq("s0"))
    srv.submit(_sq("s1"))
    with pytest.raises(QueueFull, match="s2"):
        srv.submit(_sq("s2"))
    # drain frees the depth again
    srv.drain()
    srv.submit(_sq("s2"))


def test_equal_shapes_group_and_flush_once(no_compile):
    cl = _FakeCluster()
    srv = _warm_server(cl, max_batch=3, pipeline=False)
    for i in range(3):
        assert srv.submit(_sq(f"s{i}")).lane == "fast"
    results = srv.drain()
    # one group of 3: every encode held its range payloads, ONE joint
    # flush covered all three surveys
    assert [(sid, h) for sid, h, _ in cl.executed] == [
        ("s0", True), ("s1", True), ("s2", True)]
    assert cl.vns.flushed == [["s0", "s1", "s2"]]
    assert results == {f"s{i}": f"result-s{i}" for i in range(3)}


def test_proofs_off_surveys_never_group():
    cl = _FakeCluster()
    srv = _warm_server(cl, max_batch=3, pipeline=False)
    for i in range(2):
        srv.submit(_sq(f"s{i}", proofs=0))
    srv.drain()
    assert [(sid, h) for sid, h, _ in cl.executed] == [
        ("s0", False), ("s1", False)]
    assert cl.vns.flushed == []


def test_max_batch_caps_the_group(no_compile):
    cl = _FakeCluster()
    srv = _warm_server(cl, max_batch=2, pipeline=False)
    for i in range(3):
        srv.submit(_sq(f"s{i}"))
    srv.drain()
    assert cl.vns.flushed == [["s0", "s1"]]  # s2 ran alone, no hold
    assert cl.executed[2][:2] == ("s2", False)


def test_encode_failure_degrades_one_survey_not_its_batch(no_compile):
    cl = _FakeCluster()
    cl.fail_encode.add("s1")
    srv = _warm_server(cl, max_batch=3, pipeline=False)
    for i in range(3):
        srv.submit(_sq(f"s{i}"))
    results = srv.drain()
    assert isinstance(results["s1"], RuntimeError)
    assert results["s0"] == "result-s0" and results["s2"] == "result-s2"
    # the joint flush proceeded over the survivors only
    assert cl.vns.flushed == [["s0", "s2"]]


def test_compile_lane_promotes_then_executes(no_compile):
    cl = _FakeCluster()
    srv = SurveyServer(cl, max_batch=2, pipeline=False,
                       compile_mode="lower")
    a = srv.submit(_sq("s0"))
    assert a.lane == "compile" and a.missing
    results = srv.drain()
    assert results == {"s0": "result-s0"}
    # the cooperative pass drove the driver (lower + the worker-op
    # execute filter), and the re-admission verdict is now fast
    modes = [m for _, m, _ in no_compile]
    assert modes == ["lower", "execute"]
    assert no_compile[1][2] is not None  # the `only` filter
    assert srv.admission_of("s0").lane == "fast"
    assert srv.timers.spans("Compile.s0")


def test_prewarm_compiles_without_enqueueing(no_compile):
    cl = _FakeCluster()
    srv = SurveyServer(cl, pipeline=False)
    a = srv.prewarm(_sq("s0"))
    assert a.lane == "fast"
    assert no_compile and cl.executed == []
    # a same-shape submit now fast-lanes immediately
    assert srv.submit(_sq("s1")).lane == "fast"


def test_pipeline_mode_verifies_on_the_worker_thread(no_compile):
    cl = _FakeCluster()
    srv = _warm_server(cl, max_batch=2, pipeline=True)
    for i in range(2):
        srv.submit(_sq(f"s{i}"))
    results = srv.drain()
    assert results == {"s0": "result-s0", "s1": "result-s1"}
    # encode on the drain (main) thread, verify on the named worker
    assert {t for _, _, t in cl.executed} == {"MainThread"}
    assert {t for _, t in cl.finalized} == {"server-verify"}


# -- saturation serving: quotas, DRR, shedding, the worker pool, resume ------

def test_quota_exceeded_is_typed_and_per_tenant(no_compile):
    srv = _warm_server(_FakeCluster(), max_depth=16, tenant_quota=2,
                       pipeline=False)
    srv.submit(_sq("a0"), tenant="a")
    srv.submit(_sq("a1"), tenant="a")
    with pytest.raises(QuotaExceeded, match="a2") as ei:
        srv.submit(_sq("a2"), tenant="a")
    assert ei.value.tenant == "a" and ei.value.quota == 2
    assert not isinstance(ei.value, QueueFull)  # distinct typed rejections
    # another tenant is unaffected by a's quota
    srv.submit(_sq("b0"), tenant="b")
    # draining frees a's quota again
    srv.drain()
    srv.submit(_sq("a2"), tenant="a")


def test_queue_full_beats_quota_and_shed_at_max_depth(no_compile):
    # max_depth is the hard bound: at depth 2 the error is QueueFull even
    # though tenant "a" is also past any would-be shed threshold
    srv = _warm_server(_FakeCluster(), max_depth=2, tenant_quota=8,
                       pipeline=False)
    srv.submit(_sq("s0"), tenant="a")
    srv.submit(_sq("s1"), tenant="a")
    with pytest.raises(QueueFull):
        srv.submit(_sq("s2"), tenant="a")


def test_drr_ordering_is_deterministic_across_servers(no_compile):
    """Two identically-configured servers fed the same interleaved
    multi-tenant stream must execute in the same (DRR-predicted)
    order: a gets its max_batch quantum, then b, then c, then back
    to a's remainder."""
    order = [("a0", "a"), ("a1", "a"), ("b0", "b"), ("a2", "a"),
             ("c0", "c"), ("b1", "b"), ("a3", "a")]
    executed = []
    for _ in range(2):
        cl = _FakeCluster()
        srv = _warm_server(cl, max_batch=2, max_depth=16, tenant_quota=8,
                           pipeline=False)
        for sid, tenant in order:
            srv.submit(_sq(sid), tenant=tenant)
        srv.drain()
        executed.append([sid for sid, _, _ in cl.executed])
    assert executed[0] == executed[1]
    assert executed[0] == ["a0", "a1", "b0", "b1", "c0", "a2", "a3"]


def test_hot_tenant_cannot_starve_the_rest(no_compile):
    cl = _FakeCluster()
    srv = _warm_server(cl, max_batch=2, max_depth=32, tenant_quota=16,
                       pipeline=False)
    for i in range(8):
        srv.submit(_sq(f"h{i}"), tenant="hot")
    srv.submit(_sq("v0"), tenant="victim")
    srv.drain()
    sids = [sid for sid, _, _ in cl.executed]
    # the victim ran right after hot's first quantum, not after its 8
    assert sids.index("v0") == 2, sids


def test_shed_rejects_with_retry_after_hint_and_drops_nothing(no_compile):
    from drynx_tpu.resilience import policy as rp

    # max_depth=8, shed fraction 0.75 -> shed past depth 6
    srv = _warm_server(_FakeCluster(), max_depth=8, tenant_quota=8,
                       shed_fraction=0.75, pipeline=False)
    for i in range(6):
        srv.submit(_sq(f"s{i}"))
    with pytest.raises(Overloaded, match="s6") as ei:
        srv.submit(_sq("s6"))
    # cold server (no completion rate yet): the hint is the clamp max
    assert ei.value.retry_after_s == rp.SHED_RETRY_MAX_S
    results = srv.drain()
    # shed never drops admitted work: all six completed
    assert sorted(results) == [f"s{i}" for i in range(6)]
    assert not any(isinstance(r, Exception) for r in results.values())
    # with completions observed, the hint is rate-derived and clamped
    for i in range(6):
        srv.submit(_sq(f"t{i}"))
    with pytest.raises(Overloaded) as ei2:
        srv.submit(_sq("t6"))
    assert rp.SHED_RETRY_MIN_S <= ei2.value.retry_after_s \
        <= rp.SHED_RETRY_MAX_S


def test_shed_fraction_one_disables_shedding(no_compile):
    srv = _warm_server(_FakeCluster(), max_depth=4, tenant_quota=8,
                       shed_fraction=1.0, pipeline=False)
    for i in range(4):
        srv.submit(_sq(f"s{i}"))  # no Overloaded below max_depth
    with pytest.raises(QueueFull):
        srv.submit(_sq("s4"))


def test_worker_pool_spawns_n_named_workers(no_compile):
    cl = _FakeCluster()
    srv = _warm_server(cl, max_batch=2, max_depth=16, tenant_quota=16,
                       pipeline=True, workers=3)
    for i in range(6):
        srv.submit(_sq(f"s{i}"))
    results = srv.drain()
    assert sorted(results) == [f"s{i}" for i in range(6)]
    assert [t.name for t in srv._workers] == [
        "server-verify", "server-verify-1", "server-verify-2"]
    # every finalize ran on a pool thread, never the drain thread
    names = {t for _, t in cl.finalized}
    assert names <= {"server-verify", "server-verify-1", "server-verify-2"}


def test_worker_pool_results_match_single_worker(no_compile):
    outs = []
    for w in (1, 3):
        cl = _FakeCluster()
        srv = _warm_server(cl, max_batch=2, pipeline=True, workers=w,
                           tenant_quota=16)
        for i in range(6):
            srv.submit(_sq(f"s{i}"))
        outs.append((srv.drain(), sorted(map(sorted, cl.vns.flushed))))
    assert outs[0] == outs[1]


def test_resume_requeues_exactly_once_with_probed_responders(no_compile):
    cl = _FakeCluster()
    cl.fail_encode.add("s1")
    cl.fail_encode_once.add("s1")  # transient: second attempt succeeds
    cl.probe_liveness = lambda: {"dp0": True, "dp1": False}
    srv = _warm_server(cl, max_batch=3, pipeline=False)
    for i in range(3):
        srv.submit(_sq(f"s{i}"))
    results = srv.drain()
    # the retried survey completed like a clean run
    assert results == {f"s{i}": f"result-s{i}" for i in range(3)}
    # first attempt unrestricted; the retry carried the probed live set
    attempts = [(sid, resp) for sid, _, resp in cl.exec_kwargs
                if sid == "s1"]
    assert attempts == [("s1", None), ("s1", ("dp0",))]
    # batch partners flushed without waiting on the retry; the retried
    # survey re-entered alone
    assert cl.vns.flushed == [["s0", "s2"]]


def test_resume_gives_up_after_max_retries(no_compile):
    from drynx_tpu.resilience import policy as rp

    cl = _FakeCluster()
    cl.fail_encode.add("s0")  # persistent failure: every attempt raises
    srv = _warm_server(cl, pipeline=False)
    srv.submit(_sq("s0"))
    results = srv.drain()
    assert isinstance(results["s0"], RuntimeError)
    attempts = [sid for sid, _, _ in cl.exec_kwargs if sid == "s0"]
    assert len(attempts) == 1 + rp.RESUME_MAX_RETRIES


def test_resume_budget_widens_for_checkpointed_surveys(no_compile):
    """PR 17: a cluster holding a phase checkpoint for the survey gets
    CHECKPOINT_MAX_RESUMES re-entries (each resumes mid-survey, not from
    scratch); a checkpoint-less survey keeps the legacy single retry."""
    from drynx_tpu.resilience import policy as rp
    from drynx_tpu.service.store import SurveyCheckpoint

    cl = _FakeCluster()
    cl.fail_encode.add("s0")          # persistent failure
    ck = SurveyCheckpoint(survey_id="s0")
    cl.checkpoint_for = lambda sid: ck if sid == "s0" else None
    srv = _warm_server(cl, pipeline=False)
    srv.submit(_sq("s0"))
    results = srv.drain()
    assert isinstance(results["s0"], RuntimeError)
    attempts = [sid for sid, _, _ in cl.exec_kwargs if sid == "s0"]
    assert len(attempts) == 1 + rp.CHECKPOINT_MAX_RESUMES
    assert rp.CHECKPOINT_MAX_RESUMES > rp.RESUME_MAX_RETRIES


def test_resume_e2e_transient_refusal_equals_clean_run():
    """Real LocalCluster (proofs off): a one-shot connect refusal on dp1
    fails the first dispatch's quorum, the resume slice re-probes (the
    refusal is spent), re-enters the queue once, and the retried result
    equals an undisturbed run's."""
    from drynx_tpu.resilience import faults
    from drynx_tpu.service.service import LocalCluster

    def boot():
        cl = LocalCluster(n_cns=1, n_dps=2, n_vns=0, seed=23,
                          dlog_limit=1000)
        rng = np.random.default_rng(9)
        for name, dp in cl.dps.items():
            dp.data = rng.integers(0, 5, size=(3,)).astype(np.int64)
        return cl

    def q(cl, sid):
        return cl.generate_survey_query("sum", query_min=0, query_max=9,
                                        proofs=0, survey_id=sid)

    clean = boot()
    srv0 = SurveyServer(clean, pipeline=False)
    srv0.submit(q(clean, "r0"))
    baseline = srv0.drain()["r0"].result

    plan = faults.FaultPlan(seed=0)
    plan.add(faults.FaultSpec(where="connect", kind="refuse",
                              target="dp1", count=1))
    faults.set_fault_plan(plan)
    try:
        cl = boot()
        srv = SurveyServer(cl, pipeline=False)
        srv.submit(q(cl, "r1"))
        res = srv.drain()["r1"]
    finally:
        faults.set_fault_plan(None)
    assert not isinstance(res, Exception), res
    assert res.result == baseline
    # the retry saw both DPs again: full membership, nothing absent
    assert res.responders == ["dp0", "dp1"] and res.absent == []


@pytest.mark.soak
def test_soak_pause_revive_episode_under_load(monkeypatch):
    """Mini pause/revive soak (tier-1, and check.sh's server tier): a
    healing partition window cuts dp1 from the client while a
    closed-loop LoadGen drives real surveys. The
    checkpointed resume lane paces its re-entries across the heal
    boundary: zero admitted surveys lost, affected surveys resumed from
    their phase checkpoint (probe counter > 1), results equal to an
    undisturbed run."""
    from drynx_tpu.resilience import faults
    from drynx_tpu.server.loadgen import LoadGen, ShapeMix
    from drynx_tpu.service.service import LocalCluster

    # resume passes must re-probe, not reuse a pre-heal verdict
    monkeypatch.setenv("DRYNX_PROBE_TTL", "0.1")

    def boot():
        cl = LocalCluster(n_cns=1, n_dps=2, n_vns=0, seed=23,
                          dlog_limit=1000)
        rng = np.random.default_rng(9)
        for _name, dp in cl.dps.items():
            dp.data = rng.integers(0, 5, size=(3,)).astype(np.int64)
        return cl

    def run(plan):
        faults.set_fault_plan(None)
        cl = boot()
        srv = SurveyServer(cl, max_batch=1, pipeline=False)
        lg = LoadGen(srv, shapes=[ShapeMix("s", proofs=0)], seed=7,
                     query_fn=lambda sid, shape: cl.generate_survey_query(
                         "sum", query_min=0, query_max=9, proofs=0,
                         survey_id=sid))
        if plan is not None:
            faults.set_fault_plan(plan)
            plan.reset_epoch()
        try:
            rep = lg.run_closed(concurrency=1, n_total=3)
        finally:
            faults.set_fault_plan(None)
        res = srv.results()
        return rep, {s: int(r.result) for s, r in res.items()}, res

    _rep, clean_sums, _ = run(None)

    plan = faults.FaultPlan(seed=7)
    plan.add(faults.FaultSpec(where="node", kind="partition", target="*",
                              peer="dp1", after_s=0.0, heal_after_s=0.4))
    rep, sums, res = run(plan)
    assert rep["lost"] == 0 and rep["errors"] == 0
    assert rep["completed"] == 3
    assert sums == clean_sums
    affected = [s for s, r in res.items() if r.resumes > 0]
    assert affected, "the heal window opened at t=0: someone must resume"
    for s in affected:
        assert res[s].phases.get("probe", 0) >= 2  # resumed, not restarted


# -- VN cross-flush: tampered neighbor isolation -----------------------------

def test_cross_flush_isolates_a_tampered_neighbor(tmp_path):
    """Two held surveys flushed in ONE cross-survey dispatch: the survey
    with a tampered payload gets its BM_FALSE, its batch neighbor stays
    fully green — per-survey verdicts split back out of the joint check."""
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.proofs import requests as rq
    from drynx_tpu.service.proof_collection import VerifyingNode

    rng = np.random.default_rng(3)
    sec0, pub0 = eg.keygen(rng)
    sec1, pub1 = eg.keygen(rng)

    def cross(payloads):
        return {sid: [d == b"good" for d in ds]
                for sid, ds in payloads.items()}

    vn = VerifyingNode("vn0", str(tmp_path / "vn0.db"),
                       {"dp0": pub0, "dp1": pub1},
                       verify_fns={"range_cross": cross,
                                   "range_joint":
                                   lambda ds, sid: [d == b"good"
                                                    for d in ds]})
    for sid in ("sv_ok", "sv_bad"):
        vn.register_survey(sid, expected_proofs=2,
                           thresholds={"range": 1.0}, expected_range=2,
                           hold_range=True)
        assert not vn.range_ready(sid)
    deliver = [("sv_ok", "dp0", sec0, b"good"), ("sv_ok", "dp1", sec1,
                                                 b"good"),
               ("sv_bad", "dp0", sec0, b"good"), ("sv_bad", "dp1", sec1,
                                                  b"evil")]
    for sid, dp, sec, data in deliver:
        req = rq.new_proof_request("range", sid, dp, "v0", 0, data, sec)
        # held: buffered, not verified yet
        assert vn.receive_proof(req) == rq.BM_RECVD
    assert vn.range_ready("sv_ok") and vn.range_ready("sv_bad")

    assert sorted(vn.flush_ranges_cross(["sv_ok", "sv_bad"])) == [
        "sv_bad", "sv_ok"]
    assert set(vn.bitmap_for("sv_ok").values()) == {rq.BM_TRUE}
    bad = vn.bitmap_for("sv_bad")
    assert bad["sv_bad/range/dp0/v0"] == rq.BM_TRUE
    assert bad["sv_bad/range/dp1/v0"] == rq.BM_FALSE
    # idempotent: a second flush is a no-op
    assert vn.flush_ranges_cross(["sv_ok", "sv_bad"]) == []


# -- transcripts -------------------------------------------------------------

def _fake_vns():
    vn0 = types.SimpleNamespace(
        name="vn0",
        bitmap_for=lambda sid: {"range-dp1": 101, "range-dp0": 100},
        stored_proofs=lambda sid: {"range-dp0": b"payload0",
                                   "range-dp1": b"payload1"})
    vn1 = types.SimpleNamespace(
        name="vn1",
        bitmap_for=lambda sid: {"range-dp0": 100},
        stored_proofs=lambda sid: {"range-dp0": b"payload0"})
    return types.SimpleNamespace(vns=[vn0, vn1])


def test_transcript_is_deterministic_and_key_sorted():
    vns = _fake_vns()
    t = survey_transcript(vns, "s0")
    lines = t.decode().splitlines()
    assert len(lines) == 3 and t.endswith(b"\n")
    # sorted per VN regardless of bitmap insertion order
    assert [ln.split()[1] for ln in lines] == [
        "range-dp0", "range-dp1", "range-dp0"]
    assert lines[0].split()[0] == "vn0" and lines[2].split()[0] == "vn1"
    assert survey_transcript(_fake_vns(), "s0") == t
    assert transcript_digest(vns, "s0") == transcript_digest(_fake_vns(),
                                                             "s0")


# -- the overlap metric ------------------------------------------------------

def test_pipeline_overlap_intersects_cross_survey_spans_only():
    tm = PhaseTimers()
    tm.span("Pipeline.encode.s0", 0.0, 2.0)
    tm.span("Pipeline.verify.s0", 2.0, 5.0)   # same sid: excluded
    tm.span("Pipeline.encode.s1", 4.0, 7.0)   # overlaps s0's verify by 1s
    tm.span("Pipeline.verify.s1", 7.0, 8.0)
    assert pipeline_overlap(tm) == pytest.approx(1.0)
    assert pipeline_overlap(PhaseTimers()) == 0.0


# -- CLI serve mode ----------------------------------------------------------

def test_cli_survey_run_serve_routes_through_the_server(monkeypatch,
                                                        capsys):
    """`survey run --local --serve N` submits N copies through
    SurveyServer and reports per-survey lane + result (proofs off: one
    cheap in-process cluster, no VNs, no compiles)."""
    import io
    import json

    from drynx_tpu.cmd import client as cli
    from drynx_tpu.cmd import toml_io

    cfg = {"nodes": [{"name": "cn0", "role": "cn",
                      "host": "127.0.0.1", "port": 0},
                     {"name": "dp0", "role": "dp",
                      "host": "127.0.0.1", "port": 0}],
           "survey": {"operation": "sum", "query_min": 0, "query_max": 9,
                      "dlog_limit": 1000}}
    monkeypatch.setattr("sys.stdin", io.StringIO(toml_io.dumps(cfg)))
    rc = cli.main(["survey", "run", "--local", "--serve", "2"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["operation"] == "sum"
    assert set(out["surveys"]) == {"cli0", "cli1"}
    for entry in out["surveys"].values():
        # proofs-off => no profile => always fast lane; sum of the DP's
        # 32 values drawn from [query_min, query_max)
        assert entry["lane"] == "fast"
        assert 0 <= entry["result"] <= 9 * 32


# -- proofs-on end-to-end (slow tier) ----------------------------------------

def _proofs_cluster(seed, data_seed):
    from drynx_tpu.service.service import LocalCluster

    cl = LocalCluster(n_cns=2, n_dps=2, n_vns=2, seed=seed,
                      dlog_limit=4000)
    rng = np.random.default_rng(data_seed)
    per_dp = {}
    for name, dp in cl.dps.items():
        # each DP's local sum must fit the tightest range spec (u=4, l=2
        # => value < 16): two values in [0, 4)
        d = rng.integers(0, 4, size=(2,)).astype(np.int64)
        dp.data = d
        per_dp[name] = d
    return cl, per_dp


def _queries(cl):
    mk = cl.generate_survey_query
    return [mk("sum", query_min=0, query_max=15, proofs=1,
               ranges=[(4, 2)], survey_id="s0"),
            mk("sum", query_min=0, query_max=15, proofs=1,
               ranges=[(4, 2)], survey_id="s1"),
            mk("sum", query_min=0, query_max=15, proofs=1,
               ranges=[(4, 3)], survey_id="s2")]


@pytest.mark.slow
def test_server_end_to_end_batched_equals_serial():
    from drynx_tpu.crypto import batching as B
    from drynx_tpu.proofs import requests as rq

    events = []
    rec = threading.Lock()

    def hook(name):
        with rec:
            events.append((name, threading.current_thread().name))

    cl, per_dp = _proofs_cluster(seed=13, data_seed=5)
    expected = int(np.sum(np.concatenate(list(per_dp.values()))))
    sqs = _queries(cl)
    srv = SurveyServer(cl, max_batch=3, pipeline=True)

    old = B.TRACE_HOOK
    B.TRACE_HOOK = hook
    try:
        srv.prewarm(sqs[0])
        lanes = [srv.submit(sq).lane for sq in sqs]
        results = srv.drain()
    finally:
        B.TRACE_HOOK = old

    # admission: the prewarmed (4,2) shape fast-lanes (twice — one
    # registry drive covers both), the (4,3) shape took the compile lane
    assert lanes == ["fast", "fast", "compile"]
    assert srv.admission_of("s2").lane == "fast"

    for sid in ("s0", "s1", "s2"):
        res = results[sid]
        assert res.result == expected, (sid, res.result)
        assert set(res.block.data.bitmap.values()) == {rq.BM_TRUE}

    # the pipeline actually overlapped encode with a neighbor's verify
    assert pipeline_overlap(srv.timers) > 0.0

    # proof work never first-traced off the drain/main thread
    off_main = sorted({(op, t) for op, t in events if t != "MainThread"})
    assert not off_main, off_main

    batched = {sid: survey_transcript(cl.vns, sid)
               for sid in ("s0", "s1", "s2")}
    assert all(batched.values())

    # the reference configuration: fresh cluster, same seeds, strictly
    # serial verification — transcripts must be byte-identical
    cl2, _ = _proofs_cluster(seed=13, data_seed=5)
    srv2 = SurveyServer(cl2, max_batch=1, pipeline=False)
    for sq in _queries(cl2):
        srv2.submit(sq)
    results2 = srv2.drain()
    for sid in ("s0", "s1", "s2"):
        assert results2[sid].result == expected
        assert survey_transcript(cl2.vns, sid) == batched[sid], sid

    # and the multi-worker pool: same seeds through a 2-worker verify
    # pool — the cross-survey flush is grouping-invariant, so the
    # transcripts stay byte-identical to both references
    cl3, _ = _proofs_cluster(seed=13, data_seed=5)
    srv3 = SurveyServer(cl3, max_batch=3, pipeline=True, workers=2)
    srv3.prewarm(_queries(cl3)[0])
    for sq in _queries(cl3):
        srv3.submit(sq)
    results3 = srv3.drain()
    for sid in ("s0", "s1", "s2"):
        assert results3[sid].result == expected
        assert survey_transcript(cl3.vns, sid) == batched[sid], sid


@pytest.mark.slow
@pytest.mark.chaos
def test_server_soak_with_killed_dp_degrades_without_poisoning():
    from drynx_tpu.proofs import requests as rq
    from drynx_tpu.resilience import faults

    plan = faults.FaultPlan(seed=0)
    plan.add(faults.FaultSpec(where="node", kind="kill", target="dp1"))
    faults.set_fault_plan(plan)
    try:
        cl, per_dp = _proofs_cluster(seed=17, data_seed=7)
        srv = SurveyServer(cl, max_batch=2, pipeline=True)
        mk = cl.generate_survey_query
        sqs = [mk("sum", query_min=0, query_max=15, proofs=1,
                  ranges=[(4, 2)], survey_id=f"c{i}", min_dp_quorum=1)
               for i in range(3)]
        srv.prewarm(sqs[0])
        for sq in sqs:
            srv.submit(sq)
        results = srv.drain()
    finally:
        faults.set_fault_plan(None)

    # every survey degraded the same way — dp1 absent, dp0's data only —
    # and every verdict stayed green: the fault never poisoned neighbors
    expected = int(per_dp["dp0"].sum())
    assert set(results) == {"c0", "c1", "c2"}
    for sid, res in results.items():
        assert not isinstance(res, Exception), (sid, res)
        assert res.result == expected
        assert res.absent == ["dp1"] and res.responders == ["dp0"]
        assert set(res.block.data.bitmap.values()) == {rq.BM_TRUE}
