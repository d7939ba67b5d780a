"""The handoff and serve drivers, called as functions at the program's
reduced widths on the CPU: every handoff restores the source's state, and
the result line carries the keys the benchmark's readers need."""
import time

import pytest

from bench import harness
from bench.rehearse import smoke_cell

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run(workload, seed=3, seconds=0.0, trace=False):
    cell = smoke_cell(workload, seed, seconds, trace)
    return cell, harness.driver(cell).run(cell, time.perf_counter())


@pytest.mark.parametrize("workload", ["smollm_360m.handoff",
                                      "granite_moe_1b_a400m.handoff"])
def test_handoff_restores_state(workload):
    cell, line = run(workload)
    assert list(line)[:5] == LINE_KEYS and list(line)[-1] == "checks"
    assert line["failed"] == 0
    assert line["checks"]["round_trip_mismatches"] == {"value": 0, "limit": 0}
    assert line["checks"]["state_mismatches"] == {"value": 0, "limit": 0}
    assert line["attempted"] >= 6  # requests and handoffs
    assert set(line["metrics"]) == {
        "output_tok_s", "precopy_round_ms", "downtime_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["checks"]["served_logit_gap"]["value"] >= 0


def test_handoff_runs_the_mix_delta_rounds(monkeypatch):
    """A handoff pushes one full image and then one delta per round that
    the mix states, each against the round before."""
    from repro.checkpoint.registry import Registry

    parents = []
    push_delta = Registry.push_delta

    def counted(self, trees, parent_id, **kw):
        parents.append(parent_id)
        r = push_delta(self, trees, parent_id, **kw)
        parents.append(r.image_id)
        return r
    monkeypatch.setattr(Registry, "push_delta", counted)
    cell = smoke_cell("smollm_360m.handoff", 3, 0.0, False)
    cell.mix = dict(cell.mix, handoff=dict(cell.mix["handoff"],
                                           delta_rounds=3))
    line = harness.driver(cell).run(cell, time.perf_counter())
    assert line["correct"] and line["failed"] == 0
    # set-up's handoff and the window's one: three rounds each, chained
    assert len(parents) == 2 * 2 * 3
    for h in (0, 6):
        assert parents[h + 2] == parents[h + 1]
        assert parents[h + 4] == parents[h + 3]


@pytest.mark.parametrize("workload", ["smollm_360m.serve",
                                      "granite_moe_1b_a400m.serve"])
def test_serve_reports_throughput(workload):
    cell, line = run(workload)
    assert list(line)[:5] == LINE_KEYS and list(line)[-1] == "checks"
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"output_tok_s", "setup_s"}
    assert line["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics():
    cell, line = run("smollm_360m.handoff", trace=True)
    assert set(line) == set(LINE_KEYS) | {"breakdown", "checks"}
    # on the CPU there is no device plane: only host-clock readers find
    # something, and the device-trace readers leave their metrics out
    assert set(line["metrics"]) == {"restore_ms", "replay_msg_s"}
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_is_refused():
    cell = harness.load_cell("smollm_360m.serve", 1, 1.0, False)
    with pytest.raises(harness.NoDevice):
        harness.devices(cell)
