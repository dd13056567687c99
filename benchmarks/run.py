"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  BENCH_SCALE=full for the
larger configuration; default is CI-sized (minutes).
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (aggregates, completion, components, cost_model,
                   fit_cost_model, latency, roofline_report, weak_scaling)
    from .common import ROWS
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    suites = [
        ("fit_cost_model (paper Tbl 3)", fit_cost_model.run),
        ("latency non-aggregate (paper Fig 10/11)", lambda: latency.run(False)),
        ("latency aggregate (paper Fig 12)", aggregates.run),
        ("cost model (paper Fig 8/9, Tbl 6)", cost_model.run),
        ("completion (paper Tbl 7)", completion.run),
        ("components (paper Fig 13)", components.run),
        ("weak scaling (paper Fig 14)", weak_scaling.run),
        ("roofline (assignment §Roofline)", roofline_report.run),
    ]
    failures = 0
    for name, fn in suites:
        print(f"#\n# === {name} ===", flush=True)
        try:
            fn()
        except Exception:
            failures += 1
            print(f"# SUITE FAILED: {name}", flush=True)
            traceback.print_exc()
    print(f"#\n# benchmarks complete: {len(ROWS)} rows, {failures} suite failures")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
