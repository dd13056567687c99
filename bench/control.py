"""The control of a cell's answer check: the reference in the system's
place, its path counts rounded to bfloat16 after every hop (the precision
below the system's float32 counts).

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed it builds the cell's graph and an open window's queries
exactly as a run does, answers every one of them twice (exact, and in
bfloat16) and prints one JSON line with the number of answers the bfloat16
control gets wrong: the check's ``mismatched`` as the control would read
it.  Host work only; run it at the cell's own size.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import gen, harness, load, reference

    cell, cfg, traffic = harness.cell_files(harness.manifest(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        g = gen.generate(cfg["graph"], seed)
        plain, _ = load.open_window(traffic, g, seed, args.seconds)
        got = reference.check_many(
            cfg["graph"], seed, harness.mode_of(cfg), harness.buckets_of(cfg),
            [(q, None) for q in plain], control="bfloat16",
            workers=reference.default_workers())
        wrong = {}
        for q, (ok, _) in zip(plain, got):
            if not ok:
                wrong[q["template"]] = wrong.get(q["template"], 0) + 1
        print(json.dumps(dict(seed=seed, checked=len(plain),
                              nonzero=sum(nz for _, nz in got),
                              mismatched=sum(wrong.values()),
                              by_template=wrong,
                              seconds=time.perf_counter() - t)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
