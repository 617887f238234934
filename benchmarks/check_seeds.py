"""Many seeds of one cell behind ONE set-up, with the control beside them.

    python3 benchmarks/check_seeds.py --workload <cell> --seeds 11,12,13 \
        --surveys 1 --control-seeds 3

Set-up is minutes and every process pays it, so the readings that the
limits in a configuration's file are set from (PERF.md) are taken in one
process: for each seed fresh rows, a fresh cluster and reference, then
`--surveys` surveys at the cell's own size through the window's own call,
and the comparison the benchmark's runs make (the first seed's first survey
also warms the process up). For the first `--control-seeds` seeds the
control is read too: the reference's own (`reference/<op>.control`), and,
where the configuration names one under `control.program_override`, the
program itself with that lower-precision path switched on. One JSON line a
seed; no result line: this is not the benchmark's command.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def _numbers(compared: dict) -> dict:
    return {k: c["value"] for k, c in compared.items()}


def read_seed(cell, sut, seed: int, surveys: int, config: dict) -> dict:
    """Fresh rows, cluster and reference for one seed; `surveys` surveys;
    the worst reading of each number, and the verdict."""
    from benchmarks.harness import cells, check, window

    data = cells.plugin(cell.root, "datagen", config["datagen"]).generate(
        config, seed)
    oracle = sut.host_oracle_calls()
    system = sut.System(config, data, seed, cells.plugin(
        cell.root, "queries", config["query"]).query_kwargs(config, data))
    records = [window.one_survey(system, sut, seed, i)
               for i in range(surveys)]
    oracle = sut.host_oracle_calls() - oracle
    del system
    reference = cells.plugin(cell.root, "reference", config["reference"])
    expected = reference.expect(config, data)
    compared = check.compare_window(config, reference, expected, records,
                                    oracle)
    return {"data": data, "reference": reference, "expected": expected,
            "records": records, "compared": compared,
            "correct": check.verdict(compared)}


if __name__ == "__main__":      # at module level: see run.py on frames
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--surveys", type=int, default=1)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.harness import cells, check, runner, sut, window

    cell = cells.load_cell(ROOT, args.workload)
    refusal = sut.chip_refusal(sut.device_facts(), cell.chips)
    if refusal:
        print(f"refused: {refusal}", file=sys.stderr)
        sys.exit(2)
    sut.enable_cache()
    config = cell.config
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        got = read_seed(cell, sut, seed, args.surveys, config)
        line = {"seed": seed, "surveys": len(got["records"]),
                "survey_s": [r.seconds for r in got["records"]],
                "correct": got["correct"],
                "numbers": _numbers(got["compared"])}
        if n < args.control_seeds:
            control = config.get("control", {})
            if "reference" in control:
                fake = got["reference"].control(
                    config, got["data"], got["expected"],
                    control["reference"])
                rec = window.SurveyRecord(0, seed, 0.0, 0.0,
                                          dict(fake, dps_missing=0), {}, [])
                compared = check.compare_window(
                    config, got["reference"], got["expected"], [rec], 0)
                line["control_reference"] = {
                    "correct": check.verdict(compared),
                    "numbers": _numbers(compared)}
            if "program_override" in control:
                over = read_seed(cell, sut, seed, 1,
                                 merge(config, control["program_override"]))
                # held against the configuration's own reference
                compared = check.compare_window(
                    config, got["reference"], got["expected"],
                    over["records"], 0)
                line["control_program"] = {
                    "correct": check.verdict(compared),
                    "numbers": _numbers(compared)}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line, default=runner.plain), flush=True)
