"""Proofs-on full-system survey tests, split from test_service_e2e so the
file can run in a process of its own: heavy compiles, slow tier (the
segfault once blamed on accumulated compiles was the process reaching
vm.max_map_count; tests/conftest.py now releases compiled programs)."""
import numpy as np
import pytest

from drynx_tpu.proofs import requests as rq
from drynx_tpu.service.service import LocalCluster

pytestmark = pytest.mark.slow  # heavy compiles; fast tier = -m 'not slow'


@pytest.fixture(scope="module")
def cluster_proofs():
    return LocalCluster(n_cns=2, n_dps=2, n_vns=2, seed=11, dlog_limit=4000)


def test_survey_with_proofs_commits_clean_bitmap(cluster_proofs):
    cl = cluster_proofs
    rng = np.random.default_rng(8)
    per_dp = []
    for dp in cl.dps.values():
        d = rng.integers(0, 10, size=(16,)).astype(np.int64)
        dp.data = d
        per_dp.append(d)
    sq = cl.generate_survey_query("sum", query_min=0, query_max=15, proofs=1,
                                  ranges=[(4, 4)])  # sums < 256
    res = cl.run_survey(sq)
    assert res.result == int(np.concatenate(per_dp).sum())
    assert res.block is not None
    codes = set(res.block.data.bitmap.values())
    assert codes == {rq.BM_TRUE}, res.block.data.bitmap
    assert cl.vns.root.chain.validate()


def test_survey_with_proofs_mixed_ranges(cluster_proofs):
    """Per-value range specs (round-1 weakness #4 / VERDICT task 7): a mean
    query proves its sum and its count against DIFFERENT (u, l) bounds
    (reference validates per-index ranges, lib/structs.go:446-533)."""
    cl = cluster_proofs
    rng = np.random.default_rng(9)
    per_dp = []
    for dp in cl.dps.values():
        d = rng.integers(0, 10, size=(16,)).astype(np.int64)
        dp.data = d
        per_dp.append(d)
    # per-DP sum < 160 <= 4^4; per-DP count = 16 < 4^3
    sq = cl.generate_survey_query("mean", query_min=0, query_max=15, proofs=1,
                                  ranges=[(4, 4), (4, 3)])
    res = cl.run_survey(sq)
    allv = np.concatenate(per_dp)
    assert res.result == pytest.approx(float(allv.mean()))
    assert res.block is not None
    assert set(res.block.data.bitmap.values()) == {rq.BM_TRUE}
