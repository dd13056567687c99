"""Find a cell's knee: serve windows at several offered rates in one process.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 2,4,6,8 --schedules 2

One set-up (graph, scheduler, warm-up) serves ``--schedules`` open-loop
windows per rate, each from its own seed (its own arrival schedule and
parameters), the cell's traffic otherwise unchanged.  For each rate it prints one
JSON line: the offered and completed rates, latency p50 and p95, and the
backlog trend (mean latency of the last third of the due queries minus
that of the first third: near 0 below the knee, growing with the window
above it).  The knee is the highest rate whose backlog does not grow; the
cell's traffic file then carries about four fifths of it as a number.
Needs the chip, as ``run.py`` does.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--schedules", type=int, default=1)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        harness.log("FAIL: no TPU")
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell, cfg, traffic = harness.cell_files(harness.manifest(), args.workload)
    g, sched = harness.prepare(cfg, traffic, args.seed, harness.CompileClock())
    rates = [float(r) for r in args.rates.split(",")]
    for k, (rate, j) in enumerate((r, j) for r in rates
                                  for j in range(args.schedules)):
        tr = dict(traffic, loop=dict(traffic["loop"], rate_qps=rate))
        loop, _ = harness.make_loop(cfg, tr, g, sched, args.seed + 1 + k,
                                    args.seconds)
        t0 = time.perf_counter()
        w = loop.run(t0)
        due = w.due
        ok = np.isfinite(w.done_t)
        lat = np.where(ok, w.done_t - (t0 + due), np.nan) * 1e3
        third = max(1, len(due) // 3)
        disp = [d for f in w.flushes for d in f.dispatches]
        print(json.dumps(dict(
            rate_qps=rate, schedule=j, due=len(due), done=int(ok.sum()),
            throughput_qps=int(ok.sum()) / (np.nanmax(w.done_t) - t0),
            latency_p50_ms=float(np.nanpercentile(lat, 50)),
            latency_p95_ms=float(np.nanpercentile(lat, 95)),
            backlog_trend_ms=float(np.nanmean(lat[-third:])
                                   - np.nanmean(lat[:third])),
            flushes=len(w.flushes),
            dispatch_ms_p50=float(np.median([d.service_s for d in disp]))
            * 1e3,
            mean_batch=float(np.mean([d.n_real for d in disp])))),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
