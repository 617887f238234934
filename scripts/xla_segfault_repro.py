"""Minimal repro hunt for the XLA:CPU accumulated-compile segfault.

What the full suite once observed (rounds 3-4, one process per file then): in a
long-lived process that has compiled enough DISTINCT nontrivial programs,
a subsequent compile can segfault inside the XLA CPU backend. Sites that
crash mid-suite pass in isolation; a process-wide compile lock and a
512 MB compile-thread stack (drynx_tpu/__init__.py) did not change it, so
the trigger is compiler-internal accumulated state, not concurrency or
stack depth. The suite routed around it with per-file process isolation —
this script is the exit criterion for that quarantine (round-4 VERDICT
weak #7): a standalone repro, independent of this repo's crypto code, that
can back an upstream jax issue or a version bisect.

Method: compile programs of the same FAMILY as the crashing sites — long
fixed-length scans of uint32 multiply/add ladders (the Montgomery-ladder
shape) — at a stream of distinct batch shapes, each one a fresh
executable, until the process dies or --max-compiles is reached.

Usage:
  JAX_PLATFORMS=cpu python scripts/xla_segfault_repro.py \
      [--max-compiles 400] [--steps 256] [--opt-level-0]
Progress goes to stderr (flush per compile), so after a crash the last
line names the executable count + shape that killed the process. Exit 0 =
no repro at this budget (also a result: record it).

Observed environment (round 4/5): jax 0.9.x CPU wheel, one-core linux box;
crashes appeared from roughly the mid-hundreds of accumulated suite
compiles. If this script exits 0 at several times that budget, the
in-repo trigger involves program CONTENT (pairing-scale graphs), and the
next repro step is replaying the suite's actual HLO dumps
(XLA_FLAGS=--xla_dump_to=...) in a fresh process via jax.export.

RESULTS so far (round 5, jax 0.9.0):
  * 500 distinct 256-step scan compiles, default opt: NO repro (310 s).
  * 250 distinct 2048-step scan compiles, opt-level 0: NO repro (47 s).
Conclusion: generic scan-ladder accumulation does NOT trigger it at 3x
the suite's compile count — the trigger involves the pairing-scale
program content (deep fp12 expression trees), not compile COUNT alone.
Next step for an upstream report: capture --xla_dump_to HLO from a
crashing suite run and replay the dump sequence in a fresh process.
The per-file isolation quarantine (pytest.ini) therefore stands, with
this boundary documented.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-compiles", type=int, default=400)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--opt-level-0", action="store_true",
                    help="add --xla_backend_optimization_level=0 (the "
                         "suite's setting)")
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.opt_level_0:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (flags +
                                   " --xla_backend_optimization_level=0")

    import jax
    import jax.numpy as jnp
    import numpy as np

    print(f"jax {jax.__version__} on {jax.devices()[0].platform}; "
          f"steps={args.steps}", file=sys.stderr, flush=True)

    def ladder(x, m):
        # fixed-length scan of a uint32 mul/add ladder — the Montgomery
        # scalar-mul shape the suite compiles at many batch sizes
        def step(c, _):
            a, b = c
            lo = (a * b) & jnp.uint32(0xFFFF)
            hi = (a >> 16) * (b & jnp.uint32(0xFFFF))
            a2 = (lo + hi + m) & jnp.uint32(0xFFFFFFFF)
            return (a2, b ^ a2), a2
        (_, _), ys = jax.lax.scan(step, (x, x + m), None, length=args.steps)
        return ys.sum(axis=0)

    t0 = time.time()
    for i in range(args.max_compiles):
        # every iteration gets a distinct leading shape -> fresh executable
        n = 3 + i
        x = jnp.asarray(np.arange(n * 16, dtype=np.uint32).reshape(n, 16))
        f = jax.jit(ladder)
        y = f(x, jnp.uint32(i + 1))
        y.block_until_ready()
        print(f"compile {i + 1}/{args.max_compiles} shape=({n},16) "
              f"ok at {time.time() - t0:.0f}s", file=sys.stderr, flush=True)
    print(f"NO REPRO at {args.max_compiles} distinct compiles "
          f"({time.time() - t0:.0f}s)", file=sys.stderr, flush=True)
    print('{"repro": false, "compiles": %d}' % args.max_compiles)


if __name__ == "__main__":
    main()
