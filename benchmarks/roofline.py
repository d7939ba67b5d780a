"""Roofline table of the dry-run model terms.

Reads results/dryrun.json (written by repro.launch.dryrun) and prints the
three terms per (arch x shape x mesh), the dominant bottleneck, the
MODEL_FLOPS/HLO_FLOPS usefulness ratio, and a one-line "what would move
the dominant term" suggestion.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List

SUGGESTIONS = {
    ("compute",): "increase per-chip batch or fuse small ops (MXU underfed)",
    ("memory",): "bf16 intermediates + flash tiling cut bytes; check remat "
                 "recompute and f32 attention buffers",
    ("collective",): "reshard: move FSDP all-gathers to bf16, overlap with "
                     "compute, or shard activations instead of replicating",
}


def load(path: str) -> List[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            rows.append(json.loads(line))
    return rows


def fmt_row(r: dict) -> str:
    if r["status"] != "OK":
        reason = r.get("reason", r.get("error", ""))[:60]
        return (f"{r['arch']:26s} {r['shape']:12s} {r['mesh']:8s} "
                f"{r['status']:5s} {reason}")
    rl = r["roofline"]
    dom = rl["dominant"]
    frac = rl["useful_flops_ratio"]
    return (f"{r['arch']:26s} {r['shape']:12s} {r['mesh']:8s} OK    "
            f"c={rl['compute_s']*1e3:9.1f}ms m={rl['memory_s']*1e3:9.1f}ms "
            f"x={rl['collective_s']*1e3:9.1f}ms dom={dom:10s} "
            f"useful={frac:5.2f}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--input", default="results/dryrun.json")
    ap.add_argument("--mesh", default="all")
    args = ap.parse_args(argv)
    rows = load(args.input)
    if args.mesh != "all":
        rows = [r for r in rows if r.get("mesh") == args.mesh]
    print(f"{'arch':26s} {'shape':12s} {'mesh':8s} stat  terms (per-chip)")
    for r in rows:
        print(fmt_row(r))
    ok = [r for r in rows if r["status"] == "OK"]
    if ok:
        doms: Dict[str, int] = {}
        for r in ok:
            doms[r["roofline"]["dominant"]] = doms.get(
                r["roofline"]["dominant"], 0) + 1
        print(f"\ndominant-term histogram: {doms}")
        worst = sorted(ok, key=lambda r: r["roofline"]["useful_flops_ratio"])[:5]
        print("lowest useful-flops ratio (hillclimb candidates):")
        for r in worst:
            print(f"  {r['arch']} x {r['shape']} x {r['mesh']}: "
                  f"{r['roofline']['useful_flops_ratio']:.3f} "
                  f"(dominant={r['roofline']['dominant']})")
        for dom in ("compute", "memory", "collective"):
            if any(r["roofline"]["dominant"] == dom for r in ok):
                print(f"to reduce '{dom}': {SUGGESTIONS[(dom,)]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
