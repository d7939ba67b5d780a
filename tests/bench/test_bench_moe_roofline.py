"""``moe_decode_roofline`` on hand-made traces: the share it reads from
known kernel times and step counts, and None where the trace holds no
routed-expert kernel or the model has no experts."""
import json
import os
import types

import pytest

from bench.harness import Run
from bench.metrics import moe_decode_roofline as reader
from bench.trace import Event, Trace

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = types.SimpleNamespace(deployment={"dtype": "bfloat16"})


@pytest.fixture(scope="module")
def granite():
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                           "configs", "granite_moe_1b_a400m.json")) as f:
        return json.load(f)["model"]


def traced(op_times, steps=2):
    """Kernel events of the given durations (ns) beside other ops, and
    ``steps`` runs of the engine's decode step."""
    ops, t = [], 0
    for i, d in enumerate(op_times):
        # an op that only takes the kernel's output as operand is no kernel
        ops += [Event(f"%moe_decode.{i} = f32[24,1,1024] custom-call()", t,
                      t + d),
                Event(f"%reshape.{i} = f32[3,8,1024] reshape(%moe_decode.{i})",
                      t + d, t + d + 500)]
        t += d + 500
    modules = [Event("jit__decode_all(123)", 0, t)] * steps
    return Trace([ops], [modules], [], (0, t))


def run_of(model, trace, peak=PEAK):
    return Run(CELL, model, peak, trace, {}, None, (0, 0))


def test_expert_bytes_at_published_widths(granite):
    # 24 layers, 8 experts per token, three 1024 x 512 bf16 matrices
    assert reader.expert_bytes(granite, 2) == 24 * 8 * 3 * 1024 * 512 * 2


def test_share_of_the_roofline(granite):
    """Two steps' floor over the kernel's time, and nothing else's."""
    floor_s = 2 * reader.expert_bytes(granite, 2) / PEAK["hbm_bytes_per_s"]
    kernel_ns = [400_000, 600_000, 1_000_000]      # 2 ms in all
    got = reader.read(run_of(granite, traced(kernel_ns)))
    assert got == pytest.approx(100 * floor_s / 2e-3)
    assert 0 < got <= 100


def test_none_without_the_kernel_or_experts(granite):
    no_kernel = Trace([[Event("%fusion.3 = bf16[3,1024] fusion()", 0, 10)]],
                      [[Event("jit__decode_all(1)", 0, 10)]], [], (0, 10))
    assert reader.read(run_of(granite, no_kernel)) is None
    dense = {k: v for k, v in granite.items() if k != "num_local_experts"}
    assert reader.read(run_of(dense, traced([1000]))) is None
    assert reader.read(run_of(granite, traced([1000]), peak=None)) is None
    assert reader.read(run_of(granite, traced([1000], steps=0))) is None
