#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload smollm_360m.handoff --seed 7 \\
        --seconds 51 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``), whose ``driver`` key names the code that
runs it (``bench/drivers/<driver>.py``). ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` profiles the first phase of the window
and reports its per-layer metrics, each read by
``bench/metrics/<name>.py``. Exits 3, printing no result, when JAX finds
no accelerator or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from bench import harness

    cell = harness.load_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        line = harness.driver(cell).run(cell, T_START)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
