"""The program's one tracer (drynx_tpu/utils/timers.py): spans with a parent,
a survey and CPU seconds; the profiler's annotations; the one jax.monitoring
listener; and the steps and counters a survey leaves, untiled and tiled."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drynx_tpu.compilecache import stats as ccstats
from drynx_tpu.encoding import tiles as enc_tiles
from drynx_tpu.service.service import LocalCluster
from drynx_tpu.utils import timers
from drynx_tpu.utils.timers import PROCESS, PhaseTimers, ProcessTracer, Span


def _tick():
    time.sleep(0.002)


def test_a_step_is_named_by_its_path_and_knows_parent_and_survey():
    tm = PhaseTimers("s-1")
    tm.start("Phase")
    with tm.step("outer"):
        with tm.step("inner"):
            _tick()
    tm.end("Phase")
    with tm.step("after"):
        pass
    by_name = {r.name: r for r in tm.records()}
    assert list(by_name) == ["Phase", "Phase/outer", "Phase/outer/inner",
                             "after"]
    assert by_name["Phase"].parent is None
    assert by_name["Phase/outer"].parent == "Phase"
    assert by_name["Phase/outer/inner"].parent == "Phase/outer"
    assert by_name["after"].parent is None
    assert all(isinstance(r, Span) and r.survey == "s-1"
               for r in by_name.values())
    inner, outer = by_name["Phase/outer/inner"], by_name["Phase/outer"]
    assert outer.t0 <= inner.t0 < inner.t1 <= outer.t1
    # computed or waited: read for a phase, and for every span of the
    # process tracer; a survey's steps leave the thread's clock alone
    phase = by_name["Phase"]
    assert 0.0 <= phase.cpu <= (phase.t1 - phase.t0) + 0.05
    assert inner.cpu is None and outer.cpu is None
    tr = ProcessTracer()
    with tr.step("setup/cluster"):
        _tick()
    (rec,) = tr.records("setup/")
    assert 0.0 <= rec.cpu <= (rec.t1 - rec.t0) + 0.05


def test_steps_stay_out_of_the_accumulated_phases():
    tm = PhaseTimers()
    tm.start("DataCollectionProtocol")
    with tm.step("enc"):
        _tick()
    tm.end("DataCollectionProtocol")
    tm.add("AllProofs", 0.5)
    assert [k for k, _ in tm.items()] == ["AllProofs",
                                          "DataCollectionProtocol"]
    assert tm.csv().splitlines()[0] == "AllProofs,DataCollectionProtocol"
    assert tm["DataCollectionProtocol/enc"] == 0.0


def test_spans_are_three_tuples_in_start_order_under_a_phase_only():
    tm = PhaseTimers()
    with tm.step("probe"):              # outside every phase: not in the view
        _tick()
    tm.start("A")
    with tm.step("x"):
        _tick()
    tm.end("A")
    t0 = time.perf_counter()
    tm.span("Pipeline.encode.s0", t0, t0 + 0.25, survey="s0")
    tm.span("Pipeline.verify.tenant/s1", t0 + 1, t0 + 2)   # an id with a slash
    spans = tm.spans()
    assert [s[0] for s in spans] == ["A", "A/x", "Pipeline.encode.s0",
                                     "Pipeline.verify.tenant/s1"]
    assert all(len(s) == 3 for s in spans)
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    assert [n for n, _, _ in tm.spans("A/")] == ["A/x"]
    assert "probe" in [r.name for r in tm.records()]
    assert tm.records("Pipeline.")[0].survey == "s0"
    assert tm["Pipeline.encode.s0"] == pytest.approx(0.25)


def test_self_seconds_is_duration_less_what_the_children_cover():
    tm = PhaseTimers()
    tm.start("P")
    _tick()
    with tm.step("a"):
        _tick()
    with tm.step("b"):
        _tick()
    tm.end("P")
    by_name = {r.name: r for r in tm.records()}
    covered = sum(by_name[n].t1 - by_name[n].t0 for n in ("P/a", "P/b"))
    whole = by_name["P"].t1 - by_name["P"].t0
    assert tm.self_seconds("P") == pytest.approx(whole - covered, abs=1e-9)
    assert 0.001 < tm.self_seconds("P") < whole
    assert tm.self_seconds("P/a") == pytest.approx(
        by_name["P/a"].t1 - by_name["P/a"].t0)


def test_each_thread_keeps_its_own_parents():
    tm = PhaseTimers()
    inside = threading.Barrier(2, timeout=10)

    def worker(name):
        with tm.step(name):
            inside.wait()               # both steps are open at once
            with tm.step("child"):
                _tick()

    threads = [threading.Thread(target=worker, args=(n,))
               for n in ("left", "right")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    parents = {r.name: r.parent for r in tm.records()}
    assert parents == {"left": None, "right": None,
                       "left/child": "left", "right/child": "right"}


def test_an_exception_leaves_no_step_open():
    tm = PhaseTimers()
    with pytest.raises(RuntimeError):
        with tm.step("outer"):
            with tm.step("inner"):
                tm.start("GradientDescent")     # a phase the exception cuts
                raise RuntimeError("planted")
    with tm.step("next"):
        pass
    assert tm.end("GradientDescent") == 0.0     # closed with its step
    assert {r.name: r.parent for r in tm.records()} == {
        "outer": None, "outer/inner": "outer",
        "GradientDescent": "outer/inner", "next": None}
    assert len(tm.records()) == 4


def test_step_is_a_decorator_too():
    tm = PhaseTimers("s-2")

    @tm.step("setup/cluster")
    def build(x):
        with tm.step("keys"):
            return x + 1

    assert build(1) == 2 and build(2) == 3
    assert [(r.name, r.parent) for r in tm.records()] == [
        ("setup/cluster", None), ("setup/cluster/keys", "setup/cluster")] * 2


def test_phases_and_steps_open_and_close_a_trace_annotation(monkeypatch):
    log = []

    class Fake:
        def __init__(self, path, survey):
            self.path, self.survey = path, survey

        def __enter__(self):
            log.append(("enter", "drynx:" + self.path, self.survey))

        def __exit__(self, *exc):
            log.append(("exit", "drynx:" + self.path, self.survey))

    monkeypatch.setattr(timers, "_trace_annotation", Fake)
    tm = PhaseTimers("sv")
    tm.start("KeySwitchingPhase")
    with tm.step("switch"):
        pass
    tm.end("KeySwitchingPhase")
    assert log == [("enter", "drynx:KeySwitchingPhase", "sv"),
                   ("enter", "drynx:KeySwitchingPhase/switch", "sv"),
                   ("exit", "drynx:KeySwitchingPhase/switch", "sv"),
                   ("exit", "drynx:KeySwitchingPhase", "sv")]


def test_the_real_annotation_is_jax_profilers_and_costs_nothing_unprofiled():
    ann = timers._trace_annotation("Decryption/dec", "sv")
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    tm = PhaseTimers("sv")
    t0 = time.perf_counter()
    for _ in range(1000):
        with tm.step("s"):
            pass
    assert (time.perf_counter() - t0) / 1000 < 200e-6   # microseconds each


def test_the_listener_is_installed_once(monkeypatch):
    from jax import monitoring

    calls = []
    monkeypatch.setattr(timers, "_LISTENER_INSTALLED", False)
    monkeypatch.setattr(monitoring, "register_event_duration_secs_listener",
                        lambda fn: calls.append(("duration", fn)))
    monkeypatch.setattr(monitoring, "register_event_listener",
                        lambda fn: calls.append(("event", fn)))
    for _ in range(3):
        timers.install_listener()
    ccstats.install_cache_listener()
    assert [kind for kind, _ in calls] == ["duration", "event"]


def test_a_jitted_function_leaves_trace_lower_and_compile_spans():
    from jax import monitoring

    timers.install_listener()

    @jax.jit
    def _traced_for_the_test(x):
        return x * 3 + 1

    x = jnp.ones(())                    # compiles its own small programs
    before = PROCESS.counters()
    hits = ccstats.STATS.listener_hits
    lowered = PROCESS["jax/lower"]
    t0 = time.perf_counter()
    y = _traced_for_the_test(x)
    t1 = time.perf_counter()
    assert float(y) == 4.0
    for kind in ("trace", "lower", "compile"):
        mine = PROCESS.records(f"jax/{kind}:_traced_for_the_test")
        assert len(mine) == 1, kind
        assert t0 <= mine[0].t0 <= mine[0].t1 <= t1
    assert PROCESS.counter("compile_requests") \
        == before.get("compile_requests", 0) + 1
    assert PROCESS["jax/lower"] > lowered
    # the cache's hit event reaches the same tracer, and STATS reads it
    monitoring.record_event(timers.CACHE_HIT_EVENT)
    assert ccstats.STATS.listener_hits == hits + 1
    assert PROCESS.counter("cache_hits") == hits + 1


def test_setup_is_kept_whole_and_a_ring_after_it(monkeypatch):
    monkeypatch.setattr(timers, "RING", 4)
    tr = ProcessTracer()
    for i in range(10):
        tr.jax_event(f"jax/trace:f{i}", 0.002)
    assert tr.seal_setup() is True and tr.seal_setup() is False
    for i in range(10, 20):
        tr.jax_event(f"jax/trace:f{i}", 0.002)
    names = [r.name for r in tr.records("jax/trace:")]
    assert names == [f"jax/trace:f{i}" for i in (*range(10), 16, 17, 18, 19)]
    assert tr["jax/trace"] == pytest.approx(0.040)
    tr.count("surveys", 3)
    tr.count("surveys")
    assert tr.counters() == {"surveys": 4} and tr.counter("none") == 0


def test_setup_folds_the_short_jax_events_and_seals_on_a_size(monkeypatch):
    """Some hundred thousand traces of inner jnp primitives lie under the
    grid's programs: counted, not kept; and a process that compiles without
    ever finishing a survey stops keeping set-up whole at a size."""
    monkeypatch.setattr(timers, "SETUP_MAX", 6)
    monkeypatch.setattr(timers, "RING", 3)
    tr = ProcessTracer()
    for _ in range(500):
        tr.jax_event("jax/trace:bitwise_and", 0.0001)
    tr.jax_event("jax/lower:add", 0.0002)
    tr.jax_event("jax/trace:_fused_ks", 0.5)
    assert [r.name for r in tr.records()] == ["jax/trace:_fused_ks"]
    folded = tr.folded()
    assert folded["jax/trace:bitwise_and"] == (500, pytest.approx(0.05))
    assert folded["jax/lower:add"] == (1, pytest.approx(0.0002))
    assert tr["jax/trace"] == pytest.approx(0.55)       # sums keep them
    (row,) = tr.setup_programs(programs=("_fused_ks",))
    assert row["inner"] == {"bitwise_and": [500, pytest.approx(0.05)]}
    # five more kept records reach SETUP_MAX: sealed with no survey
    for i in range(5):
        tr.jax_event(f"jax/compile:p{i}", 0.01)
    assert tr.seal_setup() is False
    for i in range(5):
        tr.jax_event(f"jax/trace:late{i}", 0.0001)  # after set-up: the ring
    assert len(tr.records()) == 6 + 3
    assert tr.folded()["jax/trace:bitwise_and"][0] == 500


def test_the_setup_report_rows_a_program_with_its_inner_traces():
    tr = ProcessTracer()
    rec = []
    fused = ("_fused_ks", "_fused_dec")
    with tr.step("setup/first_survey"):
        time.sleep(0.03)
        tr.jax_event("jax/trace:add", 0.0001)       # folded under its holder
        tr.jax_event("jax/trace:_scalar_mul_flat", 0.004)   # inner, nested
        tr.jax_event("jax/trace:_scalar_mul_flat", 0.004)
        tr.jax_event("jax/trace:add", 0.0001)
        tr.jax_event("jax/trace:_fused_ks", 0.020)          # holds them all
        # reported late, so it seems to begin before its neighbour ended:
        # a span of its own all the same
        tr.jax_event("jax/trace:_fused_dec", 0.0015)
        tr.jax_event("jax/lower:_fused_ks", 0.001)
        time.sleep(0.01)
        tr.jax_event(timers.CACHE_HIT_SPAN, 0.0)
        tr.jax_event("jax/compile:_fused_ks", 0.005)
        tr.jax_event("jax/trace:other", 0.002)      # under a second, unnamed
        tr.count("compile_requests")
        tr.count("cache_hits")
    assert tr.setup_programs(rec) == []             # none over a second
    rows = {r["program"]: r for r in tr.setup_programs(rec, fused)}
    assert set(rows) == set(fused)
    assert rows["_fused_dec"]["trace_s"] == pytest.approx(0.0015)
    assert rows["_fused_dec"]["inner"] == {}
    ks = rows["_fused_ks"]
    assert ks["inner"] == {"_scalar_mul_flat": [2, pytest.approx(0.008)],
                           "add": [2, pytest.approx(0.0002)]}
    assert ks["trace_s"] == pytest.approx(0.020)
    assert ks["compiles"] == 1 and ks["cache_hits"] == 1
    assert ks["within"][0] == "setup/first_survey"
    report = tr.setup_report(rec, fused)
    assert "_fused_ks" in report and "_scalar_mul_flat x2 0.0s" in report
    assert "hit" in report and "1 persistent-cache hits" in report
    assert "2 short jax events folded" in report


# --- a survey at the benchmark tests' size: 64 buckets, 4 DPs ---------------

N_DPS, BUCKETS = 4, 64
STEPS = {
    None: ["probe", "checkpoint", "fetch", "decode", "finalize"],
    "DataCollectionProtocol": ["local_stats", "randomness", "enc"],
    "AggregationPhase": ["reduce", "canon"],
    "KeySwitchingPhase": ["secrets", "randomness", "pass", "finish"],
    "Decryption": ["dec"],
}
CTS_BYTES = N_DPS * BUCKETS * 2 * 3 * 16 * 4


def _survey():
    cluster = LocalCluster(n_cns=3, n_dps=N_DPS, n_vns=3, seed=11,
                           dlog_limit=16, precompile="off")
    values = [5, 61, 17, 40]
    for dp, v in zip(cluster.dps.values(), values):
        dp.data = np.asarray([v], dtype=np.int64)
    sq = cluster.generate_survey_query("max", query_min=0,
                                       query_max=BUCKETS - 1, proofs=0)
    before = PROCESS.counters()
    result = cluster.run_survey(sq, seed=3)
    # what THIS survey counted: a counter an earlier test of the process
    # left behind (a diffp survey's `dro_encryptions`) did not move
    counted = {k: v - before.get(k, 0) for k, v in PROCESS.counters().items()
               if v != before.get(k, 0)}
    assert result.result == max(values)
    assert bool(np.all(result.decrypted.found))
    return result, counted


@pytest.mark.parametrize("tiled", [False, True], ids=["untiled", "tiled"])
def test_a_survey_leaves_every_step_inside_its_parent(tiled, monkeypatch):
    steps = {k: list(v) for k, v in STEPS.items()}
    if tiled:
        # the real threshold, 8192 buckets, is minutes on a CPU
        monkeypatch.setattr(enc_tiles, "TILE_THRESHOLD", 32)
        monkeypatch.setattr(enc_tiles, "DEFAULT_TILE", 32)
        monkeypatch.delenv(enc_tiles.ENV_TILE, raising=False)
        steps["DataCollectionProtocol/enc"] = ["upload", "tile0", "tile1",
                                               "regroup"]
    result, counted = _survey()
    tm = result.timers
    records = tm.records()
    assert {r.survey for r in records} == {result.survey_id}
    by_name = {}
    for r in records:
        by_name.setdefault(r.name, []).append(r)
    for parent, names in steps.items():
        for step in names:
            path = f"{parent}/{step}" if parent else step
            assert path in by_name, path
            for r in by_name[path]:
                assert r.parent == parent
                if parent:
                    (p,) = by_name[parent]
                    assert p.t0 <= r.t0 <= r.t1 <= p.t1, path
    assert len(by_name["checkpoint"]) == 6      # probe, 4 phases, done
    # the steps of collect cover it; what is left is its self time
    (collect,) = by_name["DataCollectionProtocol"]
    assert tm.self_seconds("DataCollectionProtocol") \
        <= 0.05 * (collect.t1 - collect.t0)
    # the phase view: phases and the steps under them, nothing else
    view = [n for n, _, _ in tm.spans()]
    assert view[0] == "DataCollectionProtocol"
    assert {n.split("/")[0] for n in view} == {
        "DataCollectionProtocol", "AggregationPhase", "KeySwitchingPhase",
        "Decryption"}
    assert [k for k, _ in tm.items()] == [
        "AggregationPhase", "DataCollectionProtocol", "Decryption",
        "KeySwitchingPhase"]
    # bytes between host and device, reckoned from the shapes: the stats
    # (int64), the CNs' and the querier's secrets (16 limbs each) up; the
    # result (the values, two bool masks) down; tiled, the ciphertexts
    # down tile by tile and up again in one piece
    up = N_DPS * BUCKETS * 8 + 3 * 16 * 4 + 16 * 4
    down = BUCKETS * (result.decrypted.values.itemsize + 1 + 1)
    assert counted["h2d_bytes"] + counted["d2h_bytes"] \
        == up + down + 2 * CTS_BYTES * bool(tiled)
    assert counted["h2d_bytes"] == up + CTS_BYTES * bool(tiled)
    assert counted["surveys"] == 1
    assert counted["ks_contributions"] == 3 * BUCKETS
    assert set(counted) <= {"h2d_bytes", "d2h_bytes", "surveys",
                            "ks_contributions", "compile_requests",
                            "cache_hits"}
