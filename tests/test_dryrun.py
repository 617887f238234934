"""The driver's multi-chip deliverable: dryrun_multichip must self-force a
CPU virtual mesh (round-1 failure mode: it initialized the TPU backend from
the driver process and died on a libtpu version mismatch).

The env-construction logic is unit-tested cheaply; the full child-process run
is the slow integration check (it compiles the whole sharded pipeline).
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import __graft_entry__ as ge  # noqa: E402


def test_child_env_forces_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("PJRT_DEVICE", "TPU")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2 --foo=1")
    env = ge._dryrun_child_env(8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_WORKER_ID" not in env
    assert "PJRT_DEVICE" not in env
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "device_count=2" not in env["XLA_FLAGS"]
    assert "--foo=1" in env["XLA_FLAGS"]
    assert env["_DRYNX_DRYRUN_CHILD"] == "1"


@pytest.mark.slow
def test_dryrun_multichip_subprocess():
    """End-to-end: exactly what the driver calls, including the child spawn."""
    # Clear the in-pytest marker so the subprocess path (the deliverable) runs.
    child_flag = os.environ.pop("_DRYNX_DRYRUN_CHILD", None)
    try:
        ge.dryrun_multichip(8)
    finally:
        if child_flag is not None:
            os.environ["_DRYNX_DRYRUN_CHILD"] = child_flag
