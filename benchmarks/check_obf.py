"""One survey's obfuscation phase at the cell's size, every pass checked.

    python3 benchmarks/check_obf.py --workload max-grid-10dp-obf.one-querier --seed 3200000011

A benchmark run sees what the querier sees: the zero pattern. This sees what
no party of a deployment sees, behind one set-up: the cell's cluster, then
one survey through `LocalCluster.run_survey`, with the program's own
`parallel/obfuscation.node_pass` wrapped so that every node's input, output
and scalars are kept. Before the first pass and after every pass the WHOLE
list is decrypted to points under the collective secret; then, pass by pass:

  - on a seeded sample of SAMPLE buckets the point after the pass has to be
    s_i times the point before it (before the first pass: count x B),
    reckoned here in plain integers (bn256 G1, y^2 = x^3 + 3, Jacobian
    double-and-add on Python ints; nothing of the program);
  - every bucket whose clear count is zero has to stay the identity, and no
    other bucket may become it;
  - no ciphertext's bytes, nor either component's, may be what they were
    before the pass;
  - the pass's input has to be the pass before's output.

Besides: the three nodes' scalars have to differ (from each other, row by
row, and within one node), the counter `obf_scalar_muls` has to read 2 V a
pass, the survey itself has to come out correct, and the cell's control
(the reference in the program's place handing over the clear counts) not
correct. A node's scalars are never multiplied with another's here: each
pass is checked on its own input.

One JSON line a step, then a line {"ok": ...}; exit 0 only if all held. No
result line: this is not the benchmark's command.
"""
import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLE = 512    # buckets a pass whose points are reckoned in plain integers

# --- bn256 G1 in plain integers: the script's own -----------------------------
U = 6518589491078791937
P = 36 * U ** 4 + 36 * U ** 3 + 24 * U ** 2 + 6 * U + 1    # the field
N = 36 * U ** 4 + 36 * U ** 3 + 18 * U ** 2 + 6 * U + 1    # the group's order
GENERATOR = (1, 2)              # on y^2 = x^3 + 3
R_INV = pow(1 << 256, -1, P)    # the device keeps residues times 2^256


def g1_double(p):
    """Jacobian doubling on y^2 = x^3 + 3 (a = 0). None is the identity."""
    if p is None or p[1] == 0:
        return None
    x, y, z = p
    a, b = x * x % P, y * y % P
    c = b * b % P
    d = 2 * ((x + b) ** 2 - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    return x3, (e * (d - x3) - 8 * c) % P, 2 * y * z % P


def g1_add(p, q):
    """Jacobian addition, every case: identity, equal, opposite."""
    if p is None:
        return q
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1z1, z2z2 = z1 * z1 % P, z2 * z2 % P
    u1, u2 = x1 * z2z2 % P, x2 * z1z1 % P
    s1, s2 = y1 * z2 * z2z2 % P, y2 * z1 * z1z1 % P
    if u1 == u2:
        return g1_double(p) if s1 == s2 else None
    h, r = (u2 - u1) % P, (s2 - s1) % P
    hh = h * h % P
    hhh, v = h * hh % P, u1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    return x3, (r * (v - x3) - s1 * hhh) % P, h * z1 * z2 % P


def g1_mul(p, k: int):
    """k * p for an affine point p = (x, y) or None, by double-and-add from
    the top bit; returns an affine point or None."""
    k %= N
    if p is None or k == 0:
        return None
    base, acc = (p[0], p[1], 1), None
    for bit in bin(k)[2:]:
        acc = g1_double(acc)
        if bit == "1":
            acc = g1_add(acc, base)
    return g1_affine(acc)


def g1_affine(p):
    if p is None or p[2] == 0:
        return None
    zi = pow(p[2], -1, P)
    return p[0] * zi * zi % P, p[1] * zi * zi * zi % P


def int_of_limbs(limbs) -> int:
    """16 little-endian limbs of 16 bits, one a uint32 lane."""
    return sum(int(v) << (16 * k) for k, v in enumerate(limbs))


def point_of_limbs(limbs):
    """The device's point (3, 16): Jacobian X, Y, Z, each a residue times
    2^256, Z = 0 the identity. Returns an affine point or None."""
    x, y, z = (int_of_limbs(row) * R_INV % P for row in limbs)
    return g1_affine((x, y, z))


# --- the check ------------------------------------------------------------------

def check_phase(config: dict, seed: int, sut, root: str = ROOT,
                note=print, sample: int = SAMPLE) -> bool:
    """Runs the survey and the checks; `note(line)` gets one dict a step."""
    import jax.numpy as jnp

    from benchmarks.harness import cells, check, window
    from drynx_tpu.crypto import elgamal as eg
    from drynx_tpu.parallel import obfuscation as obf
    from drynx_tpu.utils.timers import PROCESS

    t_start = time.perf_counter()
    data = cells.plugin(root, "datagen", config["datagen"]).generate(
        config, seed)
    reference = cells.plugin(root, "reference", config["reference"])
    expected = reference.expect(config, data)
    counts = expected["decrypted"]
    system = sut.System(config, data, seed, cells.plugin(
        root, "queries", config["query"]).query_kwargs(config, data))
    cluster = system.cluster
    n_cns, size = len(cluster.cns), int(counts.shape[0])
    ok = True

    def held(line: dict, *conditions) -> None:
        nonlocal ok
        line["held"] = all(bool(c) for c in conditions)
        ok = ok and line["held"]
        line["since_start_s"] = time.perf_counter() - t_start
        note(line)

    # the survey, every node's pass kept as the program made it
    passes = []
    real = obf.node_pass

    def keeping(key, cts, tm=None, prove=None):
        t0 = time.perf_counter()
        out, s = real(key, cts, tm=tm, prove=prove)
        passes.append((cts, out, s, time.perf_counter() - t0))
        return out, s

    counted = PROCESS.counter("obf_scalar_muls")
    obf.node_pass = keeping
    try:
        record = window.one_survey(system, sut, seed, 0)
    finally:
        obf.node_pass = real
    made = PROCESS.counter("obf_scalar_muls") - counted
    compared = check.compare_window(
        config, reference, expected, [record], sut.host_oracle_calls())
    held({"step": "survey", "seconds": record.seconds,
          "correct": check.verdict(compared), "passes": len(passes),
          "numbers": {k: c["value"] for k, c in compared.items()}},
         record.outputs is not None, check.verdict(compared),
         len(passes) == n_cns)
    held({"step": "counter", "obf_scalar_muls": made},
         made == 2 * size * n_cns)
    if len(passes) != n_cns:
        return False

    x = jnp.asarray(eg.secret_to_limbs(
        sum(c.secret for c in cluster.cns) % N))

    def points_of(cts) -> np.ndarray:
        """(V, 3, 16): every ciphertext decrypted to its point."""
        return np.asarray(eg.decrypt_point(cts, x))

    def is_identity(points) -> np.ndarray:
        return (points[:, 2, :] == 0).all(axis=-1)

    rng = np.random.default_rng(seed)
    before = points_of(passes[0][0])
    picked = rng.choice(size, size=min(sample, size), replace=False)
    wrong = sum(point_of_limbs(before[i])
                != g1_mul(GENERATOR, int(counts[i])) for i in picked)
    held({"step": "aggregate", "sampled": len(picked),
          "points_wrong": int(wrong),
          "zero_counts": int((counts == 0).sum()),
          "identity_pattern_diff": int(
              (is_identity(before) != (counts == 0)).sum())},
         wrong == 0, np.array_equal(is_identity(before), counts == 0))

    previous_out = None
    for ci, (cts, out, s, seconds) in enumerate(passes):
        chained = previous_out is None or cts is previous_out \
            or np.array_equal(np.asarray(cts), np.asarray(previous_out))
        was, now = np.asarray(cts), np.asarray(out)
        unchanged = int((now == was).all(axis=(1, 2, 3)).sum())
        halves_unchanged = int((now == was).all(axis=(2, 3)).sum())
        after = points_of(out)
        scalars = np.asarray(s)
        picked = rng.choice(size, size=min(sample, size), replace=False)
        wrong = sum(
            point_of_limbs(after[i]) != g1_mul(
                point_of_limbs(before[i]), int_of_limbs(scalars[i]))
            for i in picked)
        pattern_diff = int((is_identity(after) != (counts == 0)).sum())
        held({"step": "node_pass", "node": ci, "seconds": seconds,
              "sampled": len(picked), "points_wrong": int(wrong),
              "sampled_nonzero": int((counts[picked] != 0).sum()),
              "identity_pattern_diff": pattern_diff,
              "ciphertexts_unchanged": unchanged,
              "components_unchanged": halves_unchanged,
              "input_is_the_pass_befores_output": bool(chained)},
             wrong == 0, pattern_diff == 0, unchanged == 0,
             halves_unchanged == 0, chained, scalars.shape == (size, 16))
        before, previous_out = after, out

    # every node its own scalars, every ciphertext its own
    rows = [np.asarray(s) for _, _, s, _ in passes]
    shared = sum(int((rows[a] == rows[b]).all(axis=1).sum())
                 for a in range(n_cns) for b in range(a + 1, n_cns))
    distinct = [len({r.tobytes() for r in node}) for node in rows]
    below_order = all(int_of_limbs(r) < N for node in rows
                      for r in node[:: max(1, size // 64)])
    held({"step": "scalars", "shared_between_nodes": shared,
          "distinct_within_node": distinct},
         shared == 0, distinct == [size] * n_cns, below_order,
         all(not np.array_equal(rows[a], rows[b])
             for a in range(n_cns) for b in range(a + 1, n_cns)))

    # the control: no node obfuscated, the clear counts come out
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
         not check.verdict(compared), over == ["nonzero_resolved"])
    return ok


if __name__ == "__main__":      # at module level: see run.py on frames
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="max-grid-10dp-obf.one-querier")
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
