"""chip_smoke.py on the CPU: the exec phase at a tiny size, and the rules the
chip path lives by — no TPU means a non-zero exit and no "ok" line, a
backend that fails to initialise raises instead of reading as "no TPU", the
host-oracle detours are counted, and the compile cache follows one rule."""
import inspect
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

from drynx_tpu.compilecache import stats as ccstats  # noqa: E402
from drynx_tpu.crypto import batching as B  # noqa: E402
from drynx_tpu.crypto import pallas_ops as po  # noqa: E402
from drynx_tpu.parallel import proof_plane as plane  # noqa: E402
from drynx_tpu.utils import cache  # noqa: E402


def test_exec_phase_tiny_matches_clear_text():
    meter = chip_smoke.CompileMeter()
    out = chip_smoke.phase_exec(meter, n_dps=2, n_records=12, d=2,
                                iterations=5, dlog_limit=2000)
    assert out["decrypted_equals_clear"]
    # the fused enc/agg/ks/dec programs are reused; only logreg.train's
    # eager GD loop compiles again
    assert out["second_run_compile_requests"] <= 1
    assert len(out["run_seconds"]) == 2


def test_main_fails_at_the_device_phase_without_a_tpu(capsys):
    assert jax.default_backend() == "cpu"
    rc = chip_smoke.main([])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc != 0
    assert [ln["phase"] for ln in lines] == ["device"]
    assert "error" in lines[0]
    assert not any(ln.get("ok") for ln in lines)


@pytest.mark.parametrize("probe", [po.available, plane.device_count],
                         ids=["pallas_ops.available",
                              "proof_plane.device_count"])
def test_backend_init_failure_propagates(probe, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("TPU backend failed to initialise")

    monkeypatch.delenv("DRYNX_NO_PALLAS", raising=False)
    monkeypatch.setattr(po, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", boom)
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        probe()


def test_host_dispatch_counts_its_detours(monkeypatch):
    assert not po.available()          # CPU: the pairing family detours

    def double_host(x):
        return x * 2

    def kernel(x):
        raise AssertionError("kernel path taken on the CPU backend")

    monkeypatch.setattr(B, "HOST_ORACLE_CALLS", {})
    fn = B.host_dispatch(double_host, (1,), kernel)
    x = np.arange(8, dtype=np.uint32).reshape(2, 4)
    for _ in range(3):
        np.testing.assert_array_equal(np.asarray(fn(x)), x * 2)
    assert B.HOST_ORACLE_CALLS == {"double_host": 3}


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["variable-set", "variable-unset"])
def test_one_compile_cache_rule(from_env, tmp_path, monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    made = []
    monkeypatch.setattr(cache.os, "makedirs",
                        lambda d, **kw: made.append(d))
    if from_env:
        monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
        assert cache.enable_compilation_cache() == str(tmp_path)
        assert updates == [] and made == []     # jax.config untouched
    else:
        monkeypatch.delenv(cache.ENV_VAR)
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert cache.enable_compilation_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
        assert made == [want]


def test_cache_hit_event_is_the_one_this_jax_records():
    """A jax that renames the event would leave the hit counters at zero in
    silence: make that a failure here."""
    from jax._src import compiler

    assert ccstats.CACHE_HIT_EVENT in inspect.getsource(compiler)
