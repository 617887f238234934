"""The program's log-reg encode against the float64 reference, entry by
entry, on the device jax gives: the witness of PERF.md's first open question.

    python3 benchmarks/check_encode.py --config pima-logreg-10dp-exec --seeds 300-399,321

For each seed: fresh Pima-shaped rows, every provider's vector through the
program's `logreg.encode_clear` and through `reference/log_reg.encoded`,
and one JSON line where any entry differs, with each such entry's distance
from a rounding tie. The last line counts seeds and entries. On a CPU no
entry differs; on the TPU, where jnp float64 is emulated, one near a tie
may. No result line: this is not the benchmark's command.
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds_of(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b or a) + 1))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="pima-logreg-10dp-exec")
    ap.add_argument("--seeds", required=True, help="e.g. 300-399,321")
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from benchmarks.harness import cells
    from drynx_tpu.models import logreg as lr

    config = cells.load_json(os.path.join(
        ROOT, "benchmarks", "configs", args.config + ".json"))
    datagen = cells.plugin(ROOT, "datagen", config["datagen"])
    reference = cells.plugin(ROOT, "reference", config["reference"])
    query = cells.plugin(ROOT, "queries", config["query"])
    seeds, entries, seeds_off, entries_off = seeds_of(args.seeds), 0, 0, 0
    for seed in seeds:
        data = datagen.generate(config, seed)
        params = query.query_kwargs(config, data)["lr_params"]
        off = []
        for dp, (X, y) in enumerate(data["per_dp"]):
            got = np.asarray(lr.encode_clear(X, y, params), dtype=np.int64)
            packed, want = reference.encoded(config, data, dp)
            entries += want.size
            for i in np.flatnonzero(got != want):
                off.append({"dp": dp, "entry": int(i), "exact": packed[i],
                            "program": int(got[i]), "reference": int(want[i]),
                            "to_tie": abs(abs(packed[i] - np.floor(packed[i]))
                                          - 0.5)})
        if off:
            seeds_off, entries_off = seeds_off + 1, entries_off + len(off)
            print(json.dumps({"seed": seed, "off": off}), flush=True)
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "seeds": len(seeds), "seeds_off": seeds_off,
                      "entries": entries, "entries_off": entries_off}))
