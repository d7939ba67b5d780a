"""Benchmark entry point: one function per paper table/figure group.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, plus
the full result files under results/.

  fig5_8   migration_sweep    — time/downtime vs rate, 4 strategies
  fig9_11  rate_scenarios     — low/mid/high rate comparisons + reductions
  fig12_14 phase_breakdown    — sub-process latency distribution
  claims   claims             — paper headline validation bands
  beyond   beyond_paper       — batched replay + registry dedup (ours)
  delta    delta_precopy      — iterative delta checkpointing (ours)
  fleet    fleet_migration    — N-pod orchestrated migration (ours)
  topo     fleet_topology     — contended-topology scenarios (ours):
                                shared-link concurrency sweep + edge WAN
  chaos    chaos              — seeded fault schedules vs scheme (ours):
                                >= 100 randomized schedules, rollback/retry
                                invariants + same-seed determinism
  serving  serving_handoff    — tail latency under migration (ours):
                                dual-serving KV-cache handoff vs stop-then-
                                replay vs cold, exactly-once audited

``--quick`` is the CI smoke profile: repeats=1, the paper rates only,
hash-fold consumers everywhere (the JAX-compute sections are skipped), and
the adaptive registry strategy exercised alongside the paper's four.  It
still writes the same results/*.json files so CI can upload them.
"""
from __future__ import annotations

import argparse
import sys
import time


def _csv(name: str, seconds: float, derived: str):
    print(f"{name},{seconds * 1e6:.1f},{derived}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke profile: 1 repeat, paper rates, no "
                         "JAX-compute sections")
    args = ap.parse_args(argv)

    t0 = time.time()
    from benchmarks.migration_sweep import run_sweep
    from benchmarks.rate_scenarios import run_scenarios
    from benchmarks.phase_breakdown import run_breakdown
    from benchmarks.claims import run_claims
    from benchmarks import constants as C

    repeats = 1 if args.quick else 3  # full paper protocol (10): benchmarks.claims
    sweep_kwargs = {}
    if args.quick:
        sweep_kwargs = {
            "strategies": ("stop_and_copy", "ms2m_individual", "ms2m_cutoff",
                           "ms2m_statefulset", "ms2m_precopy",
                           "ms2m_adaptive"),
            "rates": C.PAPER_RATES,
        }

    t = time.time()
    sweep = run_sweep(repeats=repeats, out_path="results/migration_sweep.json",
                      **sweep_kwargs)
    for r in sweep:
        if r["rate"] in C.PAPER_RATES:
            _csv(f"fig5_8/{r['strategy']}@{r['rate']:g}",
                 r["migration_time_mean"],
                 f"downtime={r['downtime_mean']}s verified={r['all_verified']}")
    print(f"# migration_sweep done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    scen = run_scenarios(repeats=repeats, out_path="results/rate_scenarios.json")
    for r in scen:
        _csv(f"fig9_11/{r['strategy']}@{r['rate']:g}",
             r["downtime_mean"],
             f"down_reduction={r['downtime_reduction_vs_sac']*100:.2f}%")
    print(f"# rate_scenarios done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    brk = run_breakdown(repeats=repeats, out_path="results/phase_breakdown.json")
    for r in brk:
        _csv(f"fig12_14/{r['strategy']}@{r['rate']:g}", r["total_s"],
             f"replay_share={r['phase_shares']['message_replay']*100:.1f}%")
    print(f"# phase_breakdown done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    claims = run_claims(repeats=repeats, out_path="results/claims.json")
    npass = sum(1 for c in claims if c["pass"])
    _csv("claims/validated", time.time() - t, f"{npass}/{len(claims)} bands pass")
    print(f"# claims done in {time.time()-t:.1f}s", file=sys.stderr)

    if not args.quick:
        t = time.time()
        from benchmarks.beyond_paper import (run_batched_replay_bench,
                                             run_dedup_bench)
        rows = run_batched_replay_bench(repeats=2,
                                        out_path="results/beyond_paper.json")
        speedup = rows[0]["measured_replay_speedup"]
        _csv("beyond/replay_speedup", 0.0, f"{speedup}x chunk-parallel replay")
        for r in rows[1:]:
            _csv(f"beyond/{r['variant']}@{r['rate']:g}",
                 r["migration_time_mean"], f"downtime={r['downtime_mean']}s")
        dd = run_dedup_bench(out_path="results/beyond_paper_dedup.json")
        for r in dd:
            _csv(f"beyond/dedup_push_{r['push']}", 0.0,
                 f"written={r['written_mb']}MB dedup={r['dedup_ratio']*100:.1f}%")
        print(f"# beyond_paper done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    from benchmarks.delta_precopy import (run_codec_comparison,
                                          run_delta_bytes, run_precopy_sweep)
    if not args.quick:  # real-JAX consumer: skipped in the smoke profile
        db = run_delta_bytes(out_path="results/delta_bytes.json")
        _csv("delta/bytes", 0.0,
             f"delta={db['delta_written_bytes']}B "
             f"({db['delta_fraction']*100:.1f}% of full) "
             f"smaller={db['delta_strictly_smaller']}")
    for r in run_precopy_sweep(repeats=1 if args.quick else 2,
                               out_path="results/delta_precopy.json"):
        _csv(f"delta/{r['profile']}@{r['rate']:g}r{r['max_rounds']}",
             r["downtime_mean"],
             f"replayed={r['replayed_mean']} "
             f"final_round_bytes={r['final_round_bytes_mean']}")
    # codec comparison: the trainer workload is real-JAX, so the smoke
    # profile runs the blob workload only
    for r in run_codec_comparison(include_trainer=not args.quick,
                                  out_path="results/delta_codecs.json"):
        _csv(f"delta/codec_{r['workload']}_{r['codec']}", 0.0,
             f"wire_reduction=x{r['wire_reduction']} "
             f"delta_rounds=x{r['delta_wire_reduction']} "
             f"verified={r['state_verified']}")
    print(f"# delta_precopy done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    from benchmarks.fleet_migration import run_fleet, run_topology
    for r in run_fleet(repeats=1 if args.quick else 2,
                       out_path="results/fleet_migration.json"):
        _csv(f"fleet/{r['scenario']}", r["span_mean"],
             f"peak_conc={r['peak_concurrency']} "
             f"max_downtime={r['max_downtime_mean']}s "
             f"verified={r['all_verified']}")
    print(f"# fleet_migration done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    # contended topologies: quick = 1 repeat, 2 sweep points, 2 edge schemes
    # (still writes/uploads results/fleet_topology.json from CI)
    for r in run_topology(repeats=1 if args.quick else 2, quick=args.quick,
                          out_path="results/fleet_topology.json"):
        _csv(f"topo/{r['scenario']}", r["span_mean"],
             f"max_downtime={r['max_downtime_mean']}s "
             f"wire={r['wire_bytes_total']}B wan={r['wan_bytes_total']}B "
             f"verified={r['all_verified']}")
    print(f"# fleet_topology done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    # chaos: >= 100 seeded fault schedules across 3 schemes, checking the
    # crash-consistency invariant on every run (also in --quick, so CI
    # exercises the rollback/retry machinery and uploads chaos.json)
    from benchmarks.chaos import run_chaos
    for r in run_chaos(quick=args.quick, out_path="results/chaos.json"):
        if r["fault_level"] == "summary":
            _csv("chaos/summary", 0.0,
                 f"{r['runs']} schedules invariant_ok={r['invariant_ok']} "
                 f"deterministic={r['deterministic']}")
            continue
        _csv(f"chaos/{r['scheme']}@{r['fault_level']}", r["exposure_s"],
             f"failed={r['n_failed']}/{r['n_migrated'] + r['n_failed']} "
             f"attempts={r['attempts']} recovered={r['recovered']} "
             f"invariant_ok={r['invariant_ok']}")
    print(f"# chaos done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    # sim engine: epoch-batched (fluid) vs per-message kernel throughput,
    # 10k-pod smoke, one timed chaos seed (also in --quick so CI uploads
    # BENCH_sim.json and the speedup gate has fresh numbers)
    from benchmarks.sim_scale import run_sim_scale
    sim_out = run_sim_scale(quick=args.quick,
                            out_path="results/BENCH_sim.json")
    st = sim_out["steady_1k"]
    _csv("sim/steady_1k", st["fluid"]["wall_s"],
         f"speedup={st['speedup']}x fluid={st['fluid']['msgs_per_wall_s']}"
         f"msg/s baseline={st['baseline']['msgs_per_wall_s']}msg/s")
    _csv("sim/poisson", sim_out["poisson"]["fluid"]["wall_s"],
         f"speedup={sim_out['poisson']['speedup']}x")
    sm = sim_out["smoke_10k"]
    _csv("sim/smoke", sm["wall_total_s"],
         f"pods={sm['n_pods']} msgs={sm['messages']} ok={sm['ok']}")
    ch = sim_out["chaos_seed"]
    _csv("sim/chaos_seed", ch["wall_s"],
         f"pods={ch['n_pods']} invariant_ok={ch['invariant_ok']}")
    print(f"# sim_scale done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    # serving: tail latency under migration (dual-serving handoff vs
    # stop-then-replay vs cold) over flat + edge_wan, plus one injected
    # mid-handoff fault with retry (also in --quick so CI exercises the
    # handoff path and uploads serving_handoff.json)
    from benchmarks.serving_handoff import run_serving_bench
    for r in run_serving_bench(quick=args.quick,
                               out_path="results/serving_handoff.json"):
        if r["scheme"] == "VERDICT":
            _csv(f"serving/verdict@{r['topology']}", r["p99_handoff"],
                 f"p99 handoff={r['p99_handoff']}s vs "
                 f"stop_then_replay={r['p99_stop_then_replay']}s "
                 f"win={r['p99_win']}")
            continue
        tag = "+fault" if "fault" in r else ""
        _csv(f"serving/{r['scheme']}@{r['topology']}{tag}",
             r["latency"]["p99"],
             f"p50={r['latency']['p50']}s p999={r['latency']['p999']}s "
             f"exactly_once={r['exactly_once']} "
             f"state_verified={r['state_verified']} lost={r['lost']}")
    print(f"# serving done in {time.time()-t:.1f}s", file=sys.stderr)

    t = time.time()
    # rebalance: predictive controller vs reactive baseline under diurnal
    # and flash-crowd arrivals + seeded chaos schedules, 3 heterogeneous
    # model state sizes (also in --quick so CI exercises the controller
    # and uploads rebalance.json)
    from benchmarks.rebalance import run_rebalance
    reb = run_rebalance(quick=args.quick, out_path="results/rebalance.json")
    for r in reb["rows"]:
        _csv(f"rebalance/{r['config']}@{r['schedule']}s{r['seed']}",
             r["downtime_avoided_s"],
             f"avoided={r['downtime_avoided_s']}qs "
             f"per_MB={r['downtime_avoided_s_per_MB_moved']} "
             f"dominates={r['dominates']}")
    _csv("rebalance/summary", 0.0,
         f"{len(reb['rows'])} cells dominates_all={reb['dominates_all']} "
         f"chaos={len(reb['chaos'])} "
         f"invariants_ok={reb['chaos_invariants_ok']}")
    print(f"# rebalance done in {time.time()-t:.1f}s", file=sys.stderr)

    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
