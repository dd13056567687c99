"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The process checks that JAX's first
device is a TPU (it never falls back to the CPU), builds the cell's graph
from the seed, warms every shape the window will use, serves the timed
window, checks every answer of the window against the plain reference (in
worker processes that generate the graph anew from the seed), and prints
one JSON object as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (the profiler traces a few whole flushes
of the window).  Progress, and the numbers compared with their limits, go
to standard error.  Without a chip, or without the system's sources next
to ``bench/``, it prints no result and exits with 1.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: FAIL: the system's sources (src/repro) are not in this "
              "checkout", file=sys.stderr)
        return 1
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # no logs in /tmp
    from bench import harness

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_PROCESS)
    if out is None:
        return 1
    for name, c in out["checks"].items():
        print(f"bench: check {name} {c['value']} {c['ok']} {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
