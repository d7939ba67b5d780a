#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload smollm_360m.serve \\
        --seeds 11 12 13 --seconds 20 --out cal.jsonl

Runs the cell once per seed in this one process, each with a short window
at the cell's own load, and writes one JSON line per seed: the program's
numbers beside their limits, and ``control_logit_gap``, the widest gap of
the token that the float8 control (``bench/reference.py``) puts first at
the same positions. A limit lies above the largest program reading over
a dozen seeds or more and below the smallest control reading.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    gaps = {}
    served_gaps = harness.served_gaps

    def with_control(*a, **k):
        gaps.update(served_gaps(*a, control=True))
        return gaps

    harness.served_gaps = with_control
    for seed in args.seeds:
        gaps.clear()
        cell = harness.load_cell(args.workload, seed, args.seconds, False)
        t0 = time.perf_counter()
        line = harness.driver(cell).run(cell, t0)
        row = {"workload": args.workload, "seed": seed,
               "correct": line["correct"], "failed": line["failed"],
               "checks": line["checks"], **gaps,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
