#!/usr/bin/env python3
"""Rehearsals of a cell that need no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py cpu --workload smollm_360m.handoff
        runs the cell's driver end to end on the CPU at the program's
        reduced widths (``get_smoke``), a few slots and short requests;

    JAX_PLATFORMS=cpu python3 bench/rehearse.py compile --workload granite_moe_1b_a400m.serve
        compiles the cell's decode step at its real size for a described
        TPU v5e (nothing runs) and prints the compiler's memory analysis.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# at reduced widths: slots, cache length and request lengths that a CPU
# run finishes in seconds
SMOKE_DEPLOYMENT = {"slots": 4, "max_seq": 128}
SMOKE_PROMPT = {"median": 12, "sigma": 1.0, "min": 2, "max": 48}
SMOKE_ANSWER = {"median": 12, "sigma": 0.8, "min": 10, "max": 40}


def smoke_cell(workload: str, seed: int, seconds: float, trace: bool):
    """The cell at the program's reduced widths, for a run on the CPU. A
    ``<config>.<traffic>`` pair that the manifest leaves out (its path
    fails on the chip) rehearses with the metrics and limits of a cell of
    the same traffic."""
    from bench import harness

    manifest = harness._json(os.path.join(ROOT, "BENCHMARK.json"))
    config, traffic = workload.rsplit(".", 1)
    if workload in {w["name"] for w in manifest["workloads"]}:
        cell = harness.load_cell(workload, seed, seconds, trace, manifest)
    else:
        sibling = next(w["name"] for w in manifest["workloads"]
                       if w["traffic"] == traffic)
        cell = harness.load_cell(sibling, seed, seconds, trace, manifest)
        cell.name = workload
        cell.config = harness._json(os.path.join(ROOT, "bench", "configs",
                                                 config + ".json"))
    cell.smoke = True
    cell.config = dict(cell.config, deployment=dict(
        cell.config["deployment"], **SMOKE_DEPLOYMENT))
    cell.mix = dict(cell.mix, prompt=SMOKE_PROMPT, answer=SMOKE_ANSWER)
    return cell


def compile_decode(workload: str) -> None:
    """Lower and compile the engine's decode step for one described v5e
    chip at the cell's real widths, slots and cache length."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, weights
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.serving.engine import _decode_all

    # the dispatch asks jax.default_backend(), which is the CPU here: steer
    # it to the TPU kernels, which is what the described chip runs
    ops._on_tpu = lambda: True

    cell = harness.load_cell(workload, 0, 1, False)
    cfg = harness.program_config(cell)
    model = harness.model_block(cell, cfg)
    dep = cell.deployment
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(weights.make, model, 0, dep["dtype"])))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(T.init_cache, cfg, dep["slots"], dep["max_seq"])))
    tok = jax.ShapeDtypeStruct((dep["slots"], 1), jnp.int32, sharding=chip)
    t = time.perf_counter()
    compiled = _decode_all.lower(params, cfg, cache, tok, tok).compile()
    print(f"compiled {workload} decode step for v5e in "
          f"{time.perf_counter() - t:.1f} s; "
          f"pallas kernels: {compiled.as_text().count('tpu_custom_call')}")
    print(compiled.memory_analysis())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("cpu", "compile"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    if args.what == "compile":
        compile_decode(args.workload)
        return 0
    from bench import harness

    cell = smoke_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(harness.driver(cell).run(cell, T_START)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
