"""Zero jit tracing on non-main threads during a proofs-on survey.

The r05 segfault class: partial_eval recurses ~1 C frame per traced
equation, the pairing kernels trace >10k equations, and worker threads get
half the main thread's C stack — first-touch tracing from an _async_proof /
dp_lists thread killed the process with no Python traceback. The fix is
structural (LocalCluster._warm_kernels dispatches the whole compilecache
registry on the main thread before any proof thread exists, plus
compilecache.trace_guard); this test pins the INVARIANT: every bucketed
trace event during a cold proofs-on survey happens on MainThread.

batching.TRACE_HOOK fires inside the wrapped fn body, which jax runs ONLY
on a jit-cache miss — the hook observes real retraces, not mere calls.
Own file so that it can be given a cold process (warm jit caches from a
sibling test would hide trace events)."""
import threading

import numpy as np
import pytest

from drynx_tpu.crypto import batching as B
from drynx_tpu.proofs import requests as rq
from drynx_tpu.service.service import LocalCluster

pytestmark = pytest.mark.slow  # proofs-on survey: pairing-heavy compiles


def test_proofs_on_survey_traces_only_on_main_thread():
    events: list[tuple[str, str]] = []
    rec_lock = threading.Lock()

    def hook(name: str) -> None:
        with rec_lock:
            events.append((name, threading.current_thread().name))

    old = B.TRACE_HOOK
    B.TRACE_HOOK = hook
    try:
        cl = LocalCluster(n_cns=2, n_dps=2, n_vns=2, seed=13,
                          dlog_limit=4000)
        rng = np.random.default_rng(5)
        per_dp = []
        for dp in cl.dps.values():
            d = rng.integers(0, 10, size=(16,)).astype(np.int64)
            dp.data = d
            per_dp.append(d)
        sq = cl.generate_survey_query("sum", query_min=0, query_max=15,
                                      proofs=1, ranges=[(4, 4)])
        res = cl.run_survey(sq)
    finally:
        B.TRACE_HOOK = old

    # the survey itself must have succeeded (clean bitmap, right answer)
    assert res.result == int(np.concatenate(per_dp).sum())
    assert set(res.block.data.bitmap.values()) == {rq.BM_TRUE}

    off_main = sorted({(op, t) for op, t in events if t != "MainThread"})
    assert not off_main, (
        f"first-touch jit tracing on worker threads (the r05 segfault "
        f"class): {off_main}")
