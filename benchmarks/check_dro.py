"""One survey's noise phase at the cell's size, the whole list checked.

    python3 benchmarks/check_dro.py --workload diffp-sum-10dp-exec.one-querier --seed 3000000011

A benchmark run sees one ciphertext of the shuffled list (the one added to
the aggregate). This sees all of them, behind one set-up: the cell's
cluster, then the phase as `LocalCluster.execute_survey` runs it, through
the program's own `parallel/dro.py` (noise values, `encrypt_noise`, one
`node_pass` for every computing node, nothing pooled). After the encryption
and after every node's pass the WHOLE list is decrypted with the collective
secret (slab by slab, through the program's `_fused_dec`) and compared with
the plain reference's list (`reference/sum_diffp.noise_list`) as a multiset;
a pass's output has to be its input permuted by the pass's permutation,
with no ciphertext's bytes left as they were; the permutation has to be one,
and not the identity. Besides: a piece of one slab of zero encryptions
against `eg.encrypt_with_tables` on zero scalars for the same r (the bytes
have to be equal: `_dro_zero_enc` leaves the 0*B ladder out), and the
cell's control (the reference in the program's place with a noise value one
quantum beyond the list), which has to come out not correct.

One JSON line a step, then a line {"ok": ...}; exit 0 only if all held. No
result line: this is not the benchmark's command.
"""
import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIECE = 128     # zero encryptions compared with the three-ladder formula


def decrypt_list(cluster, cts, slab: int) -> tuple:
    """(values, found) of the whole list under the collective secret."""
    import jax
    import jax.numpy as jnp

    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.crypto import params
    from drynx_tpu.service import service as svc

    x = jnp.asarray(eg.secret_to_limbs(
        sum(c.secret for c in cluster.cns) % params.N))
    dl = cluster.dlog
    values, found = [], []
    size = int(cts.shape[0])
    for a in range(0, size, slab):
        # the offset an operand: one small program, not one a slab
        part = jax.lax.dynamic_slice_in_dim(cts, a, min(slab, size - a))
        v, f, _ = svc._fused_dec(part, x, dl.keys, dl.xs, dl.ysign,
                                 dl.vals)
        values.append(np.asarray(v))
        found.append(np.asarray(f))
    return np.concatenate(values).astype(np.int64), np.concatenate(found)


def check_phase(config: dict, seed: int, sut, root: str = ROOT,
                note=print) -> bool:
    """Runs the phase and the checks; `note(line)` gets one dict a step."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import cells, check, window
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import dro
    from drynx_tpu.utils.timers import PROCESS

    t_start = time.perf_counter()
    data = cells.plugin(root, "datagen", config["datagen"]).generate(
        config, seed)
    reference = cells.plugin(root, "reference", config["reference"])
    expected = reference.expect(config, data)
    query = cells.plugin(root, "queries", config["query"]).query_kwargs(
        config, data)
    cluster = sut.System(config, data, seed, query).cluster
    d = query["diffp"]
    size, n_cns = int(d.noise_list_size), len(cluster.cns)
    slab = dro.slab_widths(size)[-1]
    want = np.sort(expected["noise"])
    ok = True

    def held(line: dict, *conditions) -> None:
        nonlocal ok
        line["held"] = all(bool(c) for c in conditions)
        ok = ok and line["held"]
        line["since_start_s"] = time.perf_counter() - t_start
        note(line)

    noise = dro.generate_noise_values(size, d.lap_mean, d.lap_scale,
                                      d.quanta, d.scale, d.limit)
    held({"step": "noise_values", "size": size,
          "distinct": int(np.unique(noise).size)},
         np.array_equal(noise, expected["noise"]))

    counted = PROCESS.counter("dro_encryptions")
    key = jax.random.PRNGKey(window.survey_seed(seed, 0))
    key, k_n = jax.random.split(key)
    t0 = time.perf_counter()
    cts = dro.encrypt_noise(k_n, cluster.coll_tbl, noise)
    seconds = time.perf_counter() - t0
    values, found = decrypt_list(cluster, cts, slab)
    held({"step": "noise_enc", "seconds": seconds,
          "dlog_missed": int((~found).sum())},
         found.all(), np.array_equal(values, noise))

    for ci in range(n_cns):
        key, k_sh = jax.random.split(key)
        t0 = time.perf_counter()
        out, perm, _ = dro.node_pass(k_sh, cts, cluster.coll_tbl.table)
        seconds = time.perf_counter() - t0
        perm = np.asarray(perm)
        before, after = np.asarray(cts)[perm], np.asarray(out)
        unchanged = int((after == before).all(axis=(1, 2, 3)).sum())
        halves_unchanged = int((after == before).all(axis=(2, 3)).sum())
        previous = values
        values, found = decrypt_list(cluster, out, slab)
        fixed = int((perm == np.arange(size)).sum())
        held({"step": "node_pass", "node": ci, "seconds": seconds,
              "dlog_missed": int((~found).sum()),
              "multiset_equal": bool(np.array_equal(np.sort(values), want)),
              "is_input_permuted": bool(np.array_equal(values,
                                                       previous[perm])),
              "ciphertexts_unchanged": unchanged,
              "components_unchanged": halves_unchanged,
              "permutation_fixed_points": fixed,
              "is_a_permutation": bool(np.array_equal(np.sort(perm),
                                                      np.arange(size)))},
             found.all(), np.array_equal(np.sort(values), want),
             np.array_equal(values, previous[perm]), unchanged == 0,
             halves_unchanged == 0, fixed < size,
             np.array_equal(np.sort(perm), np.arange(size)))
        cts = out
    made = PROCESS.counter("dro_encryptions") - counted
    held({"step": "counter", "dro_encryptions": made},
         made == size * (1 + n_cns))

    # a piece of one slab of zero encryptions against today's formula
    r = eg.random_scalars(jax.random.PRNGKey(seed % (2 ** 31)), (slab,))
    tbl = cluster.coll_tbl.table
    got = np.asarray(dro._dro_zero_enc(eg.BASE_TABLE.table, tbl, r))
    n = min(PIECE, slab)
    zeros = eg.int_to_scalar(jnp.zeros((n,), dtype=jnp.int64))
    today = np.asarray(eg.encrypt_with_tables(eg.BASE_TABLE.table, tbl,
                                              zeros, r[:n]))
    held({"step": "zero_enc_bytes", "compared": n,
          "differing": int((got[:n] != today).any(axis=(1, 2, 3)).sum())},
         np.array_equal(got[:n], today))

    # the control: a noise value one quantum beyond the published list
    fake = reference.control(config, data, expected,
                             config["control"]["reference"])
    record = window.SurveyRecord(0, seed, 0.0, 0.0,
                                 dict(fake, dps_missing=0), {}, [])
    compared = check.compare_window(config, reference, expected, [record],
                                    0)
    held({"step": "control", "kind": config["control"]["reference"],
          "correct": check.verdict(compared),
          "numbers": {k: c["value"] for k, c in compared.items()}},
         not check.verdict(compared))
    return ok


if __name__ == "__main__":      # at module level: see run.py on frames
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="diffp-sum-10dp-exec.one-querier")
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
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
