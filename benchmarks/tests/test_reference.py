"""The plain references against hand-worked cases."""
import numpy as np
import pytest

from benchmarks.harness import cells
from conftest import ROOT

MAX = cells.plugin(ROOT, "reference", "max")
LOGREG = cells.plugin(ROOT, "reference", "log_reg")


def test_max_by_hand():
    config = {"query_min": 0, "n_buckets": 8}
    data = {"per_dp": [np.array([2]), np.array([5]), np.array([3])]}
    want = MAX.expect(config, data)
    # at bucket g: providers whose value lies above g
    assert want["decrypted"].tolist() == [3, 3, 2, 1, 1, 0, 0, 0]
    assert want["answer"] == 5
    good = {"values": want["decrypted"], "found": np.ones(8, bool),
            "result": 5}
    assert MAX.compare(config, want, good) == {
        "decrypted_diff_max": 0, "dlog_missed": 0, "answer_diff": 0}
    bad = dict(good, values=want["decrypted"] + np.eye(8, dtype=np.int64)[3],
               result=4)
    got = MAX.compare(config, want, bad)
    assert got["decrypted_diff_max"] == 1 and got["answer_diff"] == 1
    missed = dict(good, found=np.arange(8) != 2)
    assert MAX.compare(config, want, missed)["dlog_missed"] == 1


def test_max_control_drops_the_holder_of_the_maximum():
    config = {"query_min": 0, "n_buckets": 8}
    data = {"per_dp": [np.array([2]), np.array([5]), np.array([3])]}
    want = MAX.expect(config, data)
    fake = MAX.control(config, data, want, "drop_max_dp")
    assert fake["result"] == 3
    got = MAX.compare(config, want, fake)
    assert got["answer_diff"] == 2 and got["decrypted_diff_max"] == 1


LR = {"k": 2, "precision": 1.0, "lambda_": 1.0, "step": 0.1,
      "max_iterations": 1, "coeffs": [-0.714761, -0.5, -0.0976419]}


def _two_rows():
    # x = 1, 3 standardised by mean 2, std 1 -> -1, +1; labels 1, 0
    return {"per_dp": [(np.array([[1.0]]), np.array([1])),
                       (np.array([[3.0]]), np.array([0]))],
            "means": (2.0,), "std_devs": (1.0,), "n_records": 2}


def test_log_reg_by_hand():
    config = {"lr": LR, "n_features": 1}
    want = LOGREG.expect(config, _two_rows())
    # T1 = (+1)(1,-1) + (-1)(1,1) = (0,-2); T2 = -(Xa^T Xa) = -2 I
    assert want["decrypted"].tolist() == [0, -2, -2, 0, 0, -2]
    # one step from 0: grad = c1 T1 / n = (0, 0.5); w = (0, -0.05), whose
    # cost 0.69063 is under the start's 0.714761, so it is kept
    np.testing.assert_allclose(want["weights"], [0.0, -0.05], atol=1e-12)


def test_log_reg_gap_and_control():
    config = {"lr": dict(LR, max_iterations=50), "n_features": 1}
    data = _two_rows()
    want = LOGREG.expect(config, data)
    good = {"values": want["decrypted"], "found": np.ones(6, bool),
            "result": want["weights"].astype(np.float32)}
    got = LOGREG.compare(config, want, good)
    assert got["decrypted_diff_max"] == 0 and got["weights_gap"] < 1e-6
    fake = LOGREG.control(config, data, want, "bfloat16")
    assert LOGREG.compare(config, want, fake)["weights_gap"] > 1e-3
    nan = dict(good, result=np.array([np.nan, 0.0]))
    assert LOGREG.compare(config, want, nan)["weights_gap"] == float("inf")


def test_log_reg_holds_a_rounding_exactly():
    # one provider, one row x = 2.5 with mean 0, std 1: T1 = (1, 2.5). The
    # float64 encoding rounds the tie to even (2); its other neighbour, which
    # an encode of lower precision may give, is one unit off and fails
    config = {"lr": LR, "n_features": 1}
    data = {"per_dp": [(np.array([[2.5]]), np.array([1]))],
            "means": (0.0,), "std_devs": (1.0,), "n_records": 1}
    packed, ints = LOGREG.encoded(config, data, 0)
    assert packed.tolist() == [1.0, 2.5, -1.0, -2.5, -2.5, -6.25]
    want = LOGREG.expect(config, data)
    assert want["decrypted"].tolist() == ints.tolist() == [1, 2, -1, -2, -2, -6]
    out = {"values": want["decrypted"].copy(), "found": np.ones(6, bool),
           "result": want["weights"]}
    assert LOGREG.compare(config, want, out)["decrypted_diff_max"] == 0
    out["values"][1] += 1
    assert LOGREG.compare(config, want, out)["decrypted_diff_max"] == 1


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_generators_repeat_by_seed(seed):
    ints = cells.plugin(ROOT, "datagen", "uniform_ints")
    config = {"roster": {"n_dps": 4}, "query_min": 0, "n_buckets": 64,
              "values_per_dp": 1}
    a, b = ints.generate(config, seed), ints.generate(config, seed)
    assert [v.tolist() for v in a["per_dp"]] == \
        [v.tolist() for v in b["per_dp"]]
    assert all(0 <= int(v[0]) < 64 for v in a["per_dp"])
    pima = cells.plugin(ROOT, "datagen", "pima_shaped")
    config = {"roster": {"n_dps": 3}, "n_features": 2, "rows_per_dp": 5}
    a, b = pima.generate(config, seed), pima.generate(config, seed)
    assert a["n_records"] == 15 and len(a["per_dp"]) == 3
    np.testing.assert_array_equal(a["per_dp"][1][0], b["per_dp"][1][0])
    assert a["per_dp"][0][0].shape == (5, 2)
