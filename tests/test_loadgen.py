"""The load plane (drynx_tpu/server/loadgen): deterministic schedules,
exact offered-vs-completed accounting, closed-loop retry typing, and the
fairness metric — all against the calibrated SyntheticCluster (no jax
work, sub-second waits)."""
import pytest

from drynx_tpu.server.loadgen import (LoadGen, ShapeMix, SyntheticCluster,
                                      fairness_ratio, poisson_schedule,
                                      prewarm_shapes, synthetic_query)
from drynx_tpu.server.scheduler import SurveyServer

SHAPES = [ShapeMix("r42", weight=2.0, ranges=((4, 2),)),
          ShapeMix("off", weight=1.0, proofs=0)]


def _server(cl=None, **kw):
    cl = cl or SyntheticCluster(encode_s=0.0005, verify_s=0.002)
    kw.setdefault("max_batch", 4)
    kw.setdefault("tenant_quota", 64)
    srv = SurveyServer(cl, **kw)
    prewarm_shapes(srv, [synthetic_query(f"w-{s.name}", proofs=s.proofs,
                                         ranges=s.ranges)
                         for s in SHAPES])
    return cl, srv


# -- schedule ---------------------------------------------------------------

def test_poisson_schedule_is_deterministic_and_bounded():
    a = poisson_schedule(50.0, 2.0, seed=7)
    b = poisson_schedule(50.0, 2.0, seed=7)
    assert a == b and a
    assert all(0.0 < t < 2.0 for t in a)
    assert a == sorted(a)
    assert poisson_schedule(50.0, 2.0, seed=8) != a


def test_poisson_burst_episode_densifies_the_window():
    base = poisson_schedule(40.0, 3.0, seed=1)
    burst = poisson_schedule(40.0, 3.0, seed=1,
                             bursts=((1.0, 2.0, 5.0),))
    in_win = len([t for t in burst if 1.0 <= t < 2.0])
    base_win = len([t for t in base if 1.0 <= t < 2.0])
    # 5x instantaneous rate: the window must be clearly denser
    assert in_win > 2 * max(base_win, 1)
    # outside the window the prefix is untouched (same rng stream until
    # the first in-window draw)
    pre = [t for t in burst if t < 1.0]
    assert pre == [t for t in base if t < 1.0][:len(pre)]


# -- open loop --------------------------------------------------------------

def test_open_loop_accounting_is_exact():
    cl, srv = _server(max_depth=64, workers=2)
    lg = LoadGen(srv, shapes=SHAPES, seed=5)
    rep = lg.run_open(150.0, 1.0)
    assert rep["offered"] == len(lg.records) > 0
    r = rep["rejected"]
    assert rep["offered"] == (rep["completed"] + rep["errors"]
                              + r["shed"] + r["quota"] + r["queue_full"]
                              + rep["lost"])
    assert rep["lost"] == 0
    assert rep["completed"] == cl.finalized
    assert rep["latency_s"]["p50"] <= rep["latency_s"]["p99"]
    # per-tenant counts cover every record
    assert sum(d["offered"] for d in rep["per_tenant"].values()) \
        == rep["offered"]


def test_open_loop_overload_sheds_typed_and_loses_nothing():
    cl, srv = _server(max_depth=8, workers=1)
    lg = LoadGen(srv, shapes=SHAPES, seed=3)
    rep = lg.run_open(400.0, 0.8)
    assert rep["rejected"]["shed"] > 0
    assert rep["lost"] == 0 and rep["errors"] == 0
    assert rep["admitted"] == rep["completed"]
    sheds = [r for r in lg.records if r.outcome == "shed"]
    assert all(r.retry_after_s > 0 for r in sheds)
    assert all(not r.admitted for r in sheds)


# -- closed loop ------------------------------------------------------------

def test_closed_loop_completes_the_requested_total():
    cl, srv = _server(max_depth=32, workers=2)
    lg = LoadGen(srv, shapes=SHAPES, seed=11)
    rep = lg.run_closed(concurrency=8, n_total=60)
    assert rep["completed"] == 60 and rep["lost"] == 0
    assert rep["throughput_sps"] > 0
    assert cl.finalized == 60


def test_closed_loop_retries_rejections_as_fresh_attempts():
    # depth 2 with 8 queriers: rejections are guaranteed; every logical
    # survey still completes exactly once
    cl, srv = _server(max_depth=2, workers=1)
    lg = LoadGen(srv, shapes=SHAPES, seed=2)
    rep = lg.run_closed(concurrency=8, n_total=24, max_backoff_s=0.02)
    assert rep["completed"] == 24 and rep["lost"] == 0
    rejected = sum(rep["rejected"].values())
    assert rejected > 0
    assert rep["offered"] == 24 + rejected
    # retries carry fresh attempt ids, so records never collide
    assert len({r.survey_id for r in lg.records}) == rep["offered"]


# -- synthetic plane + fairness metric --------------------------------------

def test_synthetic_cluster_transient_failure_is_resumed():
    cl = SyntheticCluster(encode_s=0.0, verify_s=0.0,
                          fail=frozenset({"f-0"}))
    srv = SurveyServer(cl, pipeline=False, tenant_quota=8)
    prewarm_shapes(srv, [synthetic_query("w")])
    srv.submit(synthetic_query("f-0"))
    res = srv.drain()
    # the scheduler's resume slice retried through probe_liveness
    assert res["f-0"] == "ok-f-0"
    assert cl.executed == 2 and cl.finalized == 1


def test_open_loop_hot_tenant_hits_its_quota_and_victims_flow():
    # an adversarial mix: one tenant offers 8x the others, over what two
    # workers at 20 ms a verify can serve. Shedding off (fraction 1.0), so
    # that quotas and deficit round-robin stand alone: the hot tenant is
    # rejected at ITS quota, typed, while every victim keeps being served
    _, srv = _server(SyntheticCluster(encode_s=0.002, verify_s=0.02),
                     max_depth=32, workers=2, tenant_quota=4,
                     shed_fraction=1.0)
    victims = ["t1", "t2"]
    lg = LoadGen(srv, shapes=SHAPES, seed=7,
                 tenants={"hot": 8.0, "t1": 1.0, "t2": 1.0})
    rep = lg.run_open(120.0, 2.0)
    assert rep["lost"] == 0 and rep["errors"] == 0
    assert rep["rejected"]["quota"] > 0 and rep["rejected"]["shed"] == 0
    assert rep["per_tenant"]["hot"]["rejected"] > 0
    assert all(rep["per_tenant"][t]["completed"] > 0 for t in victims)
    assert fairness_ratio(rep, victims) >= 0.4


def test_fairness_ratio_bounds():
    rep = {"per_tenant": {"a": {"completed": 10}, "b": {"completed": 5},
                          "hot": {"completed": 400}}}
    assert fairness_ratio(rep, ["a", "b"]) == pytest.approx(0.5)
    assert fairness_ratio(rep, ["a", "missing"]) == 0.0
    assert fairness_ratio({"per_tenant": {}}, ["a"]) == 0.0
