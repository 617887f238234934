"""Query of an op over a bucket grid on a roster of many computing nodes:
the grid's two ends (`queries/grid.py`'s arguments). A data provider sends
one encrypted value a bucket; every computing node of the roster then makes
its own key-switch contribution on the whole aggregate.

A program from before `parallel/keyswitch.py` switches keys in ONE dispatch
over (n_cns, n_buckets) lanes, which at this roster is past what one Pallas
call and the chip's memory take: this module says so in set-up, before any
tracing, and that is the parent's clean failure in the cell."""
import importlib


def query_kwargs(config: dict, data: dict) -> dict:
    try:
        programs = importlib.import_module(
            "drynx_tpu.parallel.keyswitch").PROGRAMS
    except (ImportError, AttributeError):
        programs = ()
    if not programs:
        lanes = int(config["roster"]["n_cns"]) * int(config["n_buckets"])
        raise RuntimeError(
            "this program cannot run the deployment: it has no stored pass "
            "a computing node (drynx_tpu.parallel.keyswitch.PROGRAMS) and "
            f"would switch keys in one dispatch of {lanes} lanes "
            f"({config['roster']['n_cns']} computing nodes x "
            f"{config['n_buckets']} buckets)")
    lo = int(config["query_min"])
    return {"query_min": lo, "query_max": lo + int(config["n_buckets"]) - 1}


def n_values(config: dict) -> int:
    return int(config["n_buckets"])
