"""bench.py supervisor contract: EXACTLY one labeled JSON line for every
child outcome — clean exit, nonzero rc, segfault, timeout — plus the
persistent-cache probe verdict mapping. All children here are stubs
(`python -c ...`), so this file never imports jax and runs in seconds."""
import json
import os
import signal
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402

PY = sys.executable


@pytest.fixture(autouse=True)
def _reset_json_contract():
    bench._JSON_DONE = False
    yield
    bench._JSON_DONE = False


# ---------------------------------------------------------------------------
# supervise_child outcomes
# ---------------------------------------------------------------------------

def test_outcome_clean_exit():
    out, rc, elapsed, stdout = bench.supervise_child(
        [PY, "-c", "print('chatty child')"], 30)
    assert out == "ok" and rc == 0
    assert "chatty" in stdout          # captured, NOT leaked to our stdout


def test_outcome_nonzero_rc():
    out, rc, _, _ = bench.supervise_child(
        [PY, "-c", "import sys; sys.exit(3)"], 30)
    assert out == "rc:3" and rc == 3


def test_outcome_segfault():
    out, rc, _, _ = bench.supervise_child(
        [PY, "-c", "import os, signal; os.kill(os.getpid(), signal.SIGSEGV)"],
        30)
    assert out == "signal:SIGSEGV" and rc == -signal.SIGSEGV


def test_outcome_timeout():
    out, rc, elapsed, _ = bench.supervise_child(
        [PY, "-c", "import time; time.sleep(60)"], 1.0)
    assert out == "timeout" and rc is None
    assert elapsed < 30                # the child was killed, not awaited


# ---------------------------------------------------------------------------
# cache-probe verdict mapping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("first,second,verdict", [
    (("rc:7", 7), ("ok", 0), "ok"),
    (("ok", 0), ("ok", 0), "ok"),
    (("rc:7", 7), ("rc:7", 7), "no_hit"),
    (("rc:7", 7), ("signal:SIGSEGV", -11), "deserialize_crash"),
    (("rc:7", 7), ("rc:3", 3), "deserialize_error"),
    (("rc:7", 7), ("timeout", None), "deserialize_timeout"),
    (("signal:SIGSEGV", -11), None, "write_crash"),
    (("rc:1", 1), None, "write_failed"),
    (("timeout", None), None, "write_timeout"),
])
def test_cache_verdicts(first, second, verdict):
    assert bench.cache_verdict(first, second) == verdict


def test_only_ok_verdict_enables_cache():
    # the supervisor's gating rule, asserted against every mapped verdict
    all_verdicts = {"ok", "no_hit", "deserialize_crash", "deserialize_error",
                    "deserialize_timeout", "write_crash", "write_failed",
                    "write_timeout"}
    enabling = {v for v in all_verdicts if v == "ok"}
    assert enabling == {"ok"}


# ---------------------------------------------------------------------------
# supervisor_result labeling: every outcome -> one well-formed record
# ---------------------------------------------------------------------------

def test_result_complete_child_passes_record_through():
    rec = {"stage": "complete",
           "metric": "encrypted_logreg_pima_10dp_proofs_on_total_seconds",
           "value": 1.23, "unit": "s", "vs_baseline": 9.9,
           "shard_timers": {"VerifyShard.shard0": 0.1}}
    out = bench.supervisor_result("ok", 0, 100.0, rec, "ok")
    assert out["metric"] == rec["metric"] and out["value"] == 1.23
    assert out["child_outcome"] == "ok"
    assert out["persistent_cache_probe"] == "ok"
    assert out["shard_timers"] == {"VerifyShard.shard0": 0.1}
    assert "stage" not in out
    json.dumps(out)                    # must serialize


def test_result_segfault_keeps_partial_attribution():
    rec = {"stage": "warmup_done", "warmup_s": 42.0,
           "compile_cache_programs": 56}
    out = bench.supervisor_result("signal:SIGSEGV", -11, 500.0, rec,
                                  "deserialize_crash")
    assert out["metric"] == "bench_child_killed_sigsegv"
    assert out["last_stage"] == "warmup_done"
    assert out["warmup_s"] == 42.0
    assert out["compile_cache_programs"] == 56
    assert out["vs_baseline"] == 0.0
    assert out["persistent_cache_probe"] == "deserialize_crash"


def test_result_timeout_and_no_record():
    out = bench.supervisor_result("timeout", None, 3300.0, {}, "ok")
    assert out["metric"] == "bench_child_timeout"
    assert out["last_stage"] == "none"


def test_result_clean_exit_without_headline():
    out = bench.supervisor_result("ok", 0, 5.0, {"stage": "starting"}, "ok")
    assert out["metric"] == "bench_child_exited_without_headline"


def test_result_nonzero_rc_strips_stale_metric_fields():
    # a child that failed after writing a complete-looking record must not
    # smuggle its metric through a nonzero exit
    rec = {"stage": "failed", "metric": "stale", "value": 1.0,
           "unit": "s", "vs_baseline": 2.0, "error": "boom"}
    out = bench.supervisor_result("rc:1", 1, 50.0, rec, "no_hit")
    assert out["metric"] == "bench_child_failed_rc1"
    assert out["error"] == "boom"


@pytest.mark.parametrize("outcome,rc,record,code", [
    ("ok", 0, {"stage": "complete", "metric": "m", "value": 1.0}, 0),
    ("ok", 0, {"stage": "starting"}, 1),
    ("rc:1", 1, {"stage": "failed", "error": "boom"}, 1),
    ("rc:1", 1, {"stage": "complete", "metric": "m", "value": 1.0}, 1),
    ("signal:SIGSEGV", -11, {"stage": "warmup_done"}, 1),
    ("timeout", None, {}, 1),
])
def test_parent_exit_code_is_zero_only_for_a_complete_record(
        outcome, rc, record, code):
    result = bench.supervisor_result(outcome, rc, 1.0, record, "ok")
    assert bench.result_exit_code(result) == code


def test_failed_proofs_on_child_has_no_fallback_metric(tmp_path):
    """The exec-only fallback is gone: a measured child whose proofs-on run
    raises files a 'failed' record and exits non-zero, and the parent's
    line for it is a failure label with a non-zero exit code."""
    path = str(tmp_path / "rec.json")
    code = (
        "import sys; sys.path.insert(0, %r); import bench\n"
        "def boom(): raise RuntimeError('proofs-on died')\n"
        "bench._proofs_on_cluster = boom\n"
        "bench._RECORD_PATH = %r\n"
        "sys.argv.append(bench.NO_CACHE_FLAG)\n"
        "sys.exit(bench.main_child())\n"
        % (os.path.dirname(os.path.abspath(bench.__file__)), path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    outcome, rc, elapsed, _ = bench.supervise_child([PY, "-c", code], 120,
                                                    env=env)
    assert outcome == "rc:1"
    rec = bench.read_record(path)
    assert rec["stage"] == "failed" and "proofs-on died" in rec["error"]
    result = bench.supervisor_result(outcome, rc, elapsed, rec, "ok")
    assert result["metric"] == "bench_child_failed_rc1"
    assert bench.result_exit_code(result) == 1


# ---------------------------------------------------------------------------
# the one-JSON-line contract + record round-trip
# ---------------------------------------------------------------------------

def test_emit_first_wins(capsys):
    bench.emit({"metric": "first"})
    bench.emit({"metric": "second"})
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    assert json.loads(out[0])["metric"] == "first"


def test_child_record_roundtrip(tmp_path, monkeypatch):
    path = str(tmp_path / "rec.json")
    monkeypatch.setattr(bench, "_RECORD_PATH", path)
    bench.write_record({"stage": "cluster_built", "x": 1})
    rec = bench.read_record(path)
    assert rec["stage"] == "cluster_built" and rec["x"] == 1
    assert "elapsed_s" in rec
    # progressive overwrite, atomically
    bench.write_record({"stage": "complete", "metric": "m"})
    assert bench.read_record(path)["stage"] == "complete"
    assert bench.read_record(str(tmp_path / "missing.json")) == {}


def test_measure_child_files_failure_record_and_parent_labels(tmp_path):
    """End-to-end through real __main__ plumbing with a stubbed child body:
    a child that dies after filing a partial record yields one labeled
    JSON line from supervisor_result."""
    path = str(tmp_path / "rec.json")
    code = (
        "import sys; sys.path.insert(0, %r); import bench\n"
        "bench._RECORD_PATH = %r\n"
        "bench.write_record({'stage': 'warmup_done', 'warmup_s': 1.0})\n"
        "import os, signal; os.kill(os.getpid(), signal.SIGSEGV)\n"
        % (os.path.dirname(os.path.abspath(bench.__file__)), path))
    outcome, rc, elapsed, _ = bench.supervise_child([PY, "-c", code], 30)
    result = bench.supervisor_result(outcome, rc, elapsed,
                                     bench.read_record(path), "ok")
    assert result["metric"] == "bench_child_killed_sigsegv"
    assert result["last_stage"] == "warmup_done"
    line = json.dumps(result)
    assert json.loads(line)["warmup_s"] == 1.0
