"""Full-suite runner with per-FILE process isolation.

XLA's CPU compiler degrades in long-lived processes: after a worker has
accumulated enough distinct compiles, the NEXT nontrivial compile segfaults
— deterministically mid-suite, while the same test passes in isolation
(observed across four full-suite attempts at the same sites; a fresh
512 MB compile-thread stack and a process-wide compile lock did not change
it, so it is compiler-internal state, not stack collision or concurrency).
pytest-xdist workers persist across files, so even `-n 2 --dist loadfile`
accumulates. This runner executes each test FILE in its own pytest
subprocess — the isolation granularity at which every test passes — and
aggregates one summary line + JSON.

Usage: python scripts/run_suite.py [-m "not slow"] [--timeout 5400]
"""
import argparse
import glob
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-m", dest="mark", default=None,
                    help="pytest -m expression (e.g. 'not slow')")
    ap.add_argument("--timeout", type=int, default=5400,
                    help="per-file timeout seconds")
    ap.add_argument("--files", nargs="*", default=None)
    args = ap.parse_args()

    files = args.files or sorted(
        glob.glob(os.path.join(HERE, "tests", "test_*.py")))
    env = dict(os.environ)

    total = {"passed": 0, "failed": 0, "skipped": 0, "error": 0}
    rows = []
    t_all = time.time()
    for f in files:
        name = os.path.basename(f)
        # -n 0: run in-process (no xdist workers) — this runner IS the
        # isolation layer; pytest.ini's -n 2 would nest workers per file
        cmd = [sys.executable, "-m", "pytest", f, "-q", "-n", "0"]
        if args.mark:
            cmd += ["-m", args.mark]
        t0 = time.time()
        try:
            r = subprocess.run(cmd, cwd=HERE, env=env, capture_output=True,
                               text=True, timeout=args.timeout)
            out = r.stdout.strip().splitlines()
            tail = out[-1] if out else ""
            rc = r.returncode
        except subprocess.TimeoutExpired:
            tail, rc = "TIMEOUT", 124
        dt = time.time() - t0
        counts = _parse(tail)
        for k in total:
            total[k] += counts.get(k, 0)
        if rc not in (0, 5) and not counts.get("failed"):
            total["error"] += 1
        rows.append({"file": name, "rc": rc, "seconds": round(dt, 1),
                     "summary": tail})
        print(f"{name:32s} rc={rc} {dt:7.1f}s  {tail}", flush=True)

    summary = {"files": rows, "totals": total,
               "wall_seconds": round(time.time() - t_all, 1),
               "mark": args.mark}
    print(json.dumps({"totals": total,
                      "wall_seconds": summary["wall_seconds"]}), flush=True)
    out_path = os.path.join(HERE, "suite_results.json")
    with open(out_path, "w") as fh:
        json.dump(summary, fh, indent=1)
    print(f"wrote {out_path}", file=sys.stderr)
    sys.exit(0 if total["failed"] == 0 and total["error"] == 0 else 1)


def _parse(tail: str) -> dict:
    import re

    counts: dict = {}
    for n, kind in re.findall(r"(\d+) (passed|failed|skipped|error)", tail):
        counts[kind] = counts.get(kind, 0) + int(n)
    return counts


if __name__ == "__main__":
    main()
