"""The benchmark's command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, jax imported once, no children, JAX_PLATFORMS left alone. It
runs on the machine it is started on and refuses (exit 2, no result line)
anything but a TPU with the Pallas kernels running as Mosaic code. The last
line of standard output is the result object; earlier lines are facts
(device, warm-up, set-up, window, reference). See README.md.

Started as a script, and `run_cell` is called from module level, not from a
`main()`: on the chip's host the same tracing and lowering took 196 s like
this, 280 s through a `main()` and 485 s under `python -m`, and the cause
is open (PERF.md, PR 25, Open questions). This is the layout that was
measured; one that differs has to be measured again.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.harness.runner import run_cell

    sys.exit(run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_PROCESS_START))
