"""One survey's key switch at the cell's size, every computing node's pass
checked.

    python3 benchmarks/check_ks.py --workload max-grid-48cn-exec.one-querier --seed 3400000011

A benchmark run sees what the querier sees: the decrypted counts. This sees
what no party of a deployment sees, behind one set-up: the cell's cluster,
then one survey through `LocalCluster.run_survey`, with the program's own
`parallel/keyswitch.node_pass` wrapped so that every node's input, scalars
and contribution are kept (on the host, SAMPLE buckets of each: the whole
of 48 nodes' would not fit beside the programs' scratch). Then, node by
node, on a seeded sample of buckets and in plain integers (`check_obf.py`'s
bn256 G1; nothing of the program):

  - U_i = r_i B and W_i = r_i Q - x_i K, with the node's own secret x_i,
    the scalars r_i the pass handed back, the querier's public key Q and
    the aggregate's K component;
  - the running sums the pass hands on are the sums it was handed plus its
    own contribution, and it was handed the pass before's;
  - no node's scalars are another's, nor repeated within a node.

Besides: the counter `ks_contributions` has to read V a pass, the survey
itself has to come out correct, the program with the last node's
contribution left out has to resolve no bucket, and the cell's control (the
reference in the program's place saying so) has to come out not correct by
`dlog_missed` alone. The nodes' secrets are never added together here: each
contribution is checked with its own.

One JSON line a step, then a line {"ok": ...}; exit 0 only if all held. No
result line: this is not the benchmark's command.
"""
import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.check_obf import (GENERATOR, N, P, g1_add,  # noqa: E402
                                  g1_affine, g1_mul, int_of_limbs,
                                  point_of_limbs)

SAMPLE = 64     # buckets a node whose points are reckoned in plain integers


def _jacobian(p):
    return None if p is None else (p[0], p[1], 1)


def g1_sum(p, q):
    """p + q of affine points (None the identity), affine."""
    return g1_affine(g1_add(_jacobian(p), _jacobian(q)))


def g1_neg(p):
    return None if p is None else (p[0], (P - p[1]) % P)


def check_phase(config: dict, seed: int, sut, root: str = ROOT,
                note=print, sample: int = SAMPLE) -> bool:
    """Runs the survey and the checks; `note(line)` gets one dict a step."""
    import jax.numpy as jnp

    from benchmarks.harness import cells, check, window
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import keyswitch as kswitch
    from drynx_tpu.utils.timers import PROCESS

    t_start = time.perf_counter()
    data = cells.plugin(root, "datagen", config["datagen"]).generate(
        config, seed)
    reference = cells.plugin(root, "reference", config["reference"])
    expected = reference.expect(config, data)
    size = int(expected["decrypted"].shape[0])
    system = sut.System(config, data, seed, cells.plugin(
        root, "queries", config["query"]).query_kwargs(config, data))
    cluster = system.cluster
    n_cns = len(cluster.cns)
    picked = np.sort(np.random.default_rng(seed).choice(
        size, size=min(sample, size), replace=False))
    ok = True

    def held(line: dict, *conditions) -> None:
        nonlocal ok
        line["held"] = all(bool(c) for c in conditions)
        ok = ok and line["held"]
        line["since_start_s"] = time.perf_counter() - t_start
        note(line)

    def rows(points) -> list:
        """The sampled buckets of a device array of points, affine ints."""
        return [point_of_limbs(p) for p in np.asarray(points)[picked]]

    # the survey, every node's pass kept as the program made it: the
    # sampled rows on the host; of the sums handed on, the last two stay
    passes, handed_on, seen, all_scalars = [], [None], {}, set()
    real_pass, real_finish = kswitch.node_pass, kswitch.finish

    def keeping(key, K0, x, q_tbl, acc=None, tm=None):
        t0 = time.perf_counter()
        out, (u, w, r) = real_pass(key, K0, x, q_tbl, acc, tm=tm)
        seen.setdefault("K0", K0)
        scalars = np.asarray(r)
        passes.append({
            "chained": acc is handed_on[-1],
            "x": int_of_limbs(np.asarray(x)), "u": rows(u), "w": rows(w),
            "k_sum": rows(out[0]), "c_sum": rows(out[1]),
            "r": [int_of_limbs(s) for s in scalars[picked]],
            "distinct_scalars": len({s.tobytes() for s in scalars}),
            "seconds": time.perf_counter() - t0})
        all_scalars.update(s.tobytes() for s in scalars)
        handed_on[:] = [handed_on[-1], out]
        return out, (u, w, r)

    def finishing(agg, acc, offset_total=0, tm=None):
        seen["finish"] = (agg, acc, offset_total)
        return real_finish(agg, acc, offset_total, tm=tm)

    counted = PROCESS.counter("ks_contributions")
    kswitch.node_pass, kswitch.finish = keeping, finishing
    try:
        record = window.one_survey(system, sut, seed, 0)
    finally:
        kswitch.node_pass, kswitch.finish = real_pass, real_finish
    made = PROCESS.counter("ks_contributions") - counted
    compared = check.compare_window(
        config, reference, expected, [record], sut.host_oracle_calls())
    held({"step": "survey", "seconds": record.seconds,
          "correct": check.verdict(compared), "passes": len(passes),
          "numbers": {k: c["value"] for k, c in compared.items()}},
         record.outputs is not None, check.verdict(compared),
         len(passes) == n_cns)
    held({"step": "counter", "ks_contributions": made},
         made == size * n_cns)
    if len(passes) != n_cns:
        return False

    # node by node, each with its own secret
    k_points = rows(seen["K0"])
    q_point = tuple(int(c) for c in cluster.client.public)
    k_sum = c_sum = [None] * len(picked)
    wrong_total = 0
    for ci, (node, made_by) in enumerate(zip(cluster.cns, passes)):
        own_secret = made_by["x"] == node.secret
        u_want = [g1_mul(GENERATOR, r) for r in made_by["r"]]
        w_want = [g1_sum(g1_mul(q_point, r),
                         g1_neg(g1_mul(k, made_by["x"])))
                  for r, k in zip(made_by["r"], k_points)]
        k_sum = [g1_sum(a, b) for a, b in zip(k_sum, u_want)]
        c_sum = [g1_sum(a, b) for a, b in zip(c_sum, w_want)]
        wrong = sum(a != b for a, b in zip(made_by["u"], u_want)) \
            + sum(a != b for a, b in zip(made_by["w"], w_want))
        sums_wrong = sum(a != b for a, b in zip(made_by["k_sum"], k_sum)) \
            + sum(a != b for a, b in zip(made_by["c_sum"], c_sum))
        wrong_total += wrong + sums_wrong
        held({"step": "node_pass", "node": ci,
              "seconds": made_by["seconds"], "sampled": len(picked),
              "points_wrong": int(wrong), "sums_wrong": int(sums_wrong),
              "own_secret": own_secret,
              "handed_the_pass_befores_sums": made_by["chained"],
              "distinct_scalars": made_by["distinct_scalars"]},
             wrong == 0, sums_wrong == 0, own_secret, made_by["chained"],
             made_by["distinct_scalars"] == size,
             all(r < N for r in made_by["r"]))

    shared = n_cns * size - len(all_scalars)
    secrets_distinct = len({p["x"] for p in passes}) == n_cns
    held({"step": "scalars", "shared_between_nodes": shared,
          "secrets_distinct": secrets_distinct,
          "points_and_sums_wrong_in_all": int(wrong_total)},
         shared == 0, secrets_distinct, wrong_total == 0)

    # the program itself with the last node's contribution left out: every
    # ciphertext still carries that node's x K, and no bucket resolves
    agg, acc, offset_total = seen["finish"]
    if n_cns > 1:
        _, _, f_dec = cluster._fused()
        dl = cluster.dlog
        xq = jnp.asarray(eg.secret_to_limbs(cluster.client.secret))
        found = [np.asarray(f_dec(
            real_finish(agg, sums, offset_total), xq, dl.keys, dl.xs,
            dl.ysign, dl.vals)[1]) for sums in (acc, handed_on[0])]
        held({"step": "one_contribution_left_out",
              "resolved_with_all": int(found[0].sum()),
              "resolved_without_the_last": int(found[1].sum())},
             acc is handed_on[1], found[0].all(), not found[1].any())

    fake = reference.control(config, data, expected,
                             config["control"]["reference"])
    fake_record = window.SurveyRecord(0, seed, 0.0, 0.0,
                                      dict(fake, dps_missing=0), {}, [])
    compared = check.compare_window(config, reference, expected,
                                    [fake_record], 0)
    over = sorted(k for k, c in compared.items() if c["value"] > c["limit"])
    held({"step": "control", "kind": config["control"]["reference"],
          "correct": check.verdict(compared), "over_their_limit": over,
          "numbers": {k: c["value"] for k, c in compared.items()}},
         not check.verdict(compared), over == ["dlog_missed"])
    return ok


if __name__ == "__main__":      # at module level: see run.py on frames
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="max-grid-48cn-exec.one-querier")
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    from benchmarks.harness import cells, runner, sut

    cell = cells.load_cell(ROOT, args.workload)
    device = sut.device_facts()
    refusal = sut.chip_refusal(device, cell.chips)
    if refusal:
        print(f"refused: {refusal}", file=sys.stderr)
        sys.exit(2)
    sut.enable_cache()
    runner.note({"phase": "device", "device": device, "seed": args.seed})
    all_held = check_phase(cell.config, args.seed, sut, note=runner.note)
    runner.note({"ok": all_held, "memory": sut.memory_stats()})
    sys.exit(0 if all_held else 1)
