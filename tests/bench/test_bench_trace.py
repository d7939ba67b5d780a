"""The trace-to-metric reduction, by hand on a few intervals and on a
small trace recorded on the chip: the device intervals and host spans,
and the per-layer readers over them."""
import gzip
import importlib
import json
import os

import pytest

from bench import trace
from bench.harness import WINDOW_SPAN, Run
from bench.trace import Event, Trace

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def made():
    ops = [Event("a", 10, 20), Event("b", 15, 30), Event("a", 50, 60),
           Event("c", 95, 120)]
    spans = [Event("registry.push", 0, 40), Event("engine.process", 40, 100),
             Event("checkpoint", 45, 55)]
    return Trace([ops], [[Event("jit__decode_all", 10, 30)]], spans, (0, 100))


def test_merge_clips_and_joins():
    assert trace.merge([(15, 30), (10, 20), (50, 60), (95, 120)], 0, 100) == [
        (10, 30), (50, 60), (95, 100)]


def test_busy_and_overlap():
    t = made()
    b = trace.busy(t)
    assert b == [(10, 30), (50, 60), (95, 100)]
    assert trace.busy_s(t) == 35e-9
    assert trace.overlap(b, 0, 40) == 20
    assert trace.overlap(b, 40, 100) == 15
    assert trace.busy(t, device=1) == []


def test_top_ops_and_idle_gaps():
    t = made()
    assert dict(map(tuple, trace.top_ops(t))) == {"a": 20e-9, "b": 15e-9,
                                                  "c": 5e-9}
    # gaps: [0,10) push, [30,50) push/engine (midpoint 40 -> engine),
    # [60,95) engine
    assert dict(map(tuple, trace.idle_by_span(t))) == {
        "registry.push": 10e-9, "engine.process": 55e-9}
    assert trace.innermost(t, 50) == "checkpoint"
    assert trace.innermost(t, 200) is None


def read(name, t, counters):
    mod = importlib.import_module("bench.metrics." + name)
    return mod.read(Run(None, MODEL, PEAK, t, counters, None, (0, 0)))


MODEL = {"num_hidden_layers": 2, "hidden_size": 8, "head_dim": 4,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "intermediate_size": 16, "vocab_size": 32}


def test_device_readers():
    t = made()
    assert read("decode_step_ms", t, {}) == pytest.approx(20e-6)
    assert read("device_idle_pct", t, {}) == pytest.approx(65.0)
    # push span [0, 40): 20 ns of device time, 20 ns of host time
    assert read("push_host_ms", t, {}) == pytest.approx(20e-6)
    # 8190 bytes take 10 ns at 819 GB/s: half of the 20 ns of device time
    assert read("codec_roofline", t, {"push_state_bytes": 8190}) == (
        pytest.approx(50.0))
    assert read("decode_lanes_per_step", t, {"all_n": 3}) == 3.0


def test_readers_with_nothing_to_read_return_none():
    t = Trace([[]], [[]], [], (0, 100))
    for name in ("decode_step_ms", "push_host_ms", "codec_roofline",
                 "precopy_mfu", "decode_attention_roofline",
                 "decode_lanes_per_step"):
        assert read(name, t, {}) is None


CHIP_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "smollm_tiny_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """A trace recorded on one TPU v5e: smollm_360m at published widths
    with 4 slots of 256 positions, one admission, a checkpoint and a full
    push inside the benchmark's window span."""
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(CHIP_TRACE) as f:
        path.write_bytes(f.read())
    return trace.load(str(path), ("engine.process", "checkpoint",
                                  "registry.push"), WINDOW_SPAN)


def test_chip_trace_reduces(chip_trace):
    t = chip_trace
    assert (len(t.ops), len(t.ops[0]), len(t.modules[0])) == (1, 12148, 1008)
    assert [s.name for s in t.spans] == ["engine.process", "checkpoint",
                                         "registry.push"]
    assert t.window_s == pytest.approx(0.850747055)
    assert trace.busy_s(t) == pytest.approx(0.026982844)
    assert dict(map(tuple, trace.idle_by_span(t))) == pytest.approx({
        "registry.push": 0.753144249, "host": 0.056378601,
        "engine.process": 0.014241361})
    assert sum("decode_attention" in e.name for e in t.ops[0]) == 384


def test_readers_find_their_names_in_the_chip_trace(chip_trace):
    """Every device-trace reader finds what it reads in a real trace: the
    engine's ``_decode_all`` module runs, the decode-attention kernel, the
    push span's device time."""
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                           "configs", "smollm_360m.json")) as f:
        model = json.load(f)["model"]
    counters = {"all_n": 7, "all_ctx": 30, "useful_n": 7, "useful_ctx": 30,
                "push_state_bytes": 4 * 10485760}
    run = Run(None, model, PEAK, chip_trace, counters, None, (0, 0))
    got = {n: importlib.import_module("bench.metrics." + n).read(run)
           for n in ("decode_step_ms", "device_idle_pct", "push_host_ms",
                     "codec_roofline", "decode_attention_roofline",
                     "decode_lanes_per_step", "decode_mfu", "precopy_mfu")}
    assert got.pop("precopy_mfu") is None  # no precopy span in this trace
    assert got["decode_step_ms"] == pytest.approx(2.344385)
    assert got["push_host_ms"] == pytest.approx(758.786305)
    assert all(v is not None and v > 0 for v in got.values())
    assert all(got[n] <= 100 for n in ("device_idle_pct", "codec_roofline",
                                        "decode_attention_roofline",
                                        "decode_mfu"))
