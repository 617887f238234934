"""CPU tests of the benchmark itself. Not under tests/: the tier-1 count is
the program's. Run with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider -p no:xdist
"""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.check_seeds import merge  # noqa: E402

DATA_DIRS = ("configs", "traffic", "metrics", "readers", "datagen",
             "queries", "reference")
# The Pima cell is out of BENCHMARK.json until the program's log-reg encode
# is exact on the TPU (PERF.md, Open questions, first row); its files stay,
# and these are the two entries that bring it back. The tests add them to
# their copy of the manifest: a configuration with a new op comes in as
# files and entries.
WAITING = {
    "configs": [{
        "name": "pima-logreg-10dp-exec",
        "source": "Drynx (Froelicher et al., IEEE TIFS 2020) logistic "
                  "regression on Pima, 768 records x 10 DP, 8 features, K=2, "
                  "450 iterations: TIFS/logRegV2.py:9-14",
        "file": "benchmarks/configs/pima-logreg-10dp-exec.json",
        "reduced": [],
        "why": "the paper's log-reg deployment at full size, proofs off: 90 "
               "ciphertexts a provider, the host does most of the work"}],
    "workloads": [{
        "name": "pima-logreg-10dp-exec.one-querier",
        "config": "pima-logreg-10dp-exec", "traffic": "one-querier",
        "chips": 1,
        "why": "one querier, closed loop, some 50 surveys of 900 ciphertexts: "
               "the chip idles about 70 %; host glue, dispatch and the "
               "per-survey re-compile show here, a kernel gain must not"}],
}
# sizes a CPU test run can hold (the CPU path takes seconds per hundred
# ciphertexts): a 64-bucket grid and a 2-iteration, 2-feature Pima, each
# over 4 data providers
SMALL = {
    "max-grid-10dp-exec": {"n_buckets": 64, "dlog_limit": 16,
                           "roster": {"n_dps": 4}},
    "pima-logreg-10dp-exec": {"rows_per_dp": 12, "n_features": 2,
                              "dlog_limit": 200, "roster": {"n_dps": 4},
                              "lr": {"max_iterations": 2}},
}


def make_copy(root: str) -> str:
    """A copy of BENCHMARK.json, with the WAITING entries added, and of the
    benchmark's data files under `root`, with every configuration cut to
    its SMALL size."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for group, entries in WAITING.items():
        bench[group] += entries
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(root, "benchmarks", d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name, over in SMALL.items():
        path = os.path.join(root, "benchmarks", "configs", name + ".json")
        with open(path) as f:
            config = merge(json.load(f), over)
        with open(path, "w") as f:
            json.dump(config, f)
    return root


@pytest.fixture
def bench_copy(tmp_path):
    return make_copy(str(tmp_path))
