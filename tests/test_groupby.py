"""Group-by query semantics (reference data_collection_protocol.go:157-196
per-group encode + same-group homomorphic aggregation; services/api.go:124-128
per-group decode).

Two tiers: (1) the grouped encoder vs looping the ungrouped encoder over each
group's subset (clear-text twin), (2) an end-to-end grouped survey with two
group attributes matching per-group clear-text results.
"""
import zlib

import numpy as np
import pytest

from drynx_tpu.encoding import stats as st
from drynx_tpu.service.service import LocalCluster

RNG = np.random.default_rng(91)

GROUP_BY = [[0, 1], [10, 20, 30]]  # 2 attributes -> 6 groups


def _rand_groups(rows, rng):
    return np.stack([rng.choice(np.asarray(vals), size=rows)
                     for vals in GROUP_BY], axis=-1).astype(np.int64)


ENCODER_OPS = ["sum", "mean", "variance", "min", "max", "frequency_count",
               "union", "inter", "bool_OR", "bool_AND", "cosim", "lin_reg"]


@pytest.mark.parametrize("op", ENCODER_OPS)
def test_grouped_encoder_matches_subset_loop(op):
    rows, qmin, qmax = 40, 0, 12
    rng = np.random.default_rng(zlib.crc32(op.encode()))
    if op == "cosim":
        data = rng.integers(0, 9, size=(rows, 2)).astype(np.int64)
    elif op == "lin_reg":
        X = rng.integers(0, 5, size=(rows, 2)).astype(np.int64)
        y = X[:, 0] + 2 * X[:, 1]
        data = np.concatenate([X, y[:, None]], axis=1)
    else:
        data = rng.integers(qmin, qmax + 1, size=(rows,)).astype(np.int64)
    groups = _rand_groups(rows, rng)
    grid = st.group_grid(GROUP_BY)

    got = np.asarray(st.encode_clear_grouped(
        op, data, groups, grid, qmin, qmax))

    for gi, g in enumerate(grid):
        m = np.all(groups == g[None, :], axis=-1)
        sub = data[m]
        if sub.shape[0] == 0:
            continue  # empty-group identities covered by the e2e decode test
        want = np.asarray(st.encode_clear(op, sub, qmin, qmax))
        np.testing.assert_array_equal(got[gi], want, err_msg=f"group {g}")


def test_group_grid_shape():
    grid = st.group_grid(GROUP_BY)
    assert grid.shape == (6, 2)
    assert {tuple(g) for g in grid} == {(a, b) for a in [0, 1]
                                        for b in [10, 20, 30]}


@pytest.fixture(scope="module")
def cluster():
    return LocalCluster(n_cns=3, n_dps=3, n_vns=0, seed=7, dlog_limit=25000)


@pytest.mark.slow
@pytest.mark.parametrize("op", ["sum", "mean", "frequency_count"])
def test_grouped_survey_matches_cleartext(cluster, op):
    rows, qmin, qmax = 20, 0, 9
    rng = np.random.default_rng(5 + zlib.crc32(op.encode()) % 1000)
    all_data, all_groups = [], []
    for dp in cluster.dps.values():
        d = rng.integers(qmin, qmax + 1, size=(rows,)).astype(np.int64)
        g = _rand_groups(rows, rng)
        dp.data, dp.groups = d, g
        all_data.append(d)
        all_groups.append(g)
    data = np.concatenate(all_data)
    groups = np.concatenate(all_groups)

    sq = cluster.generate_survey_query(
        op, query_min=qmin, query_max=qmax, group_by=GROUP_BY)
    res = cluster.run_survey(sq)

    assert set(res.result.keys()) == {tuple(g) for g in st.group_grid(GROUP_BY)}
    for g, r in res.result.items():
        m = np.all(groups == np.asarray(g)[None, :], axis=-1)
        sub = data[m]
        if op == "sum":
            assert r == int(sub.sum()), g
        elif op == "mean":
            if sub.size == 0:
                assert r is None, g
            else:
                assert r == pytest.approx(float(sub.mean())), g
        elif op == "frequency_count":
            want = {v: int((sub == v).sum()) for v in range(qmin, qmax + 1)}
            assert r == want, g
