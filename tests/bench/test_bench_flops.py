"""The benchmark's operation and byte counts against hand-computed values
at the published smollm_360m and granite_moe_1b_a400m widths."""
import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                       "configs")


def model(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name, params", [
    # 32 x (2*960*960 + 2*960*320 + 3*960*2560) + 960*49152
    ("smollm_360m", 32 * (1_843_200 + 614_400 + 7_372_800) + 47_185_920),
    # 24 x (2*1024*1024 + 2*1024*512 + 1024*32 + 8*3*1024*512) + 1024*49155
    ("granite_moe_1b_a400m",
     24 * (2_097_152 + 1_048_576 + 32_768 + 12_582_912) + 50_334_720),
])
def test_matmul_params(name, params):
    assert flops.matmul_params(model(name)) == params


@pytest.mark.parametrize("name, per_ctx", [
    ("smollm_360m", 4 * 32 * 15 * 64),
    ("granite_moe_1b_a400m", 4 * 24 * 16 * 64),
])
def test_attention_flops(name, per_ctx):
    assert flops.attention_flops(model(name), 1000) == 1000 * per_ctx


@pytest.mark.parametrize("name, want", [
    # layers x bf16 x (K and V of 1000 positions + q and out of 10 passes)
    ("smollm_360m", 32 * 2 * (2 * 5 * 64 * 1000 + 2 * 15 * 64 * 10)),
    ("granite_moe_1b_a400m", 24 * 2 * (2 * 8 * 64 * 1000 + 2 * 16 * 64 * 10)),
])
def test_attention_bytes(name, want):
    assert flops.attention_bytes(model(name), 10, 1000, 2) == want


def test_step_flops_adds_matmuls_and_attention():
    m = model("smollm_360m")
    assert flops.step_flops(m, 3, 600) == (
        2 * 3 * flops.matmul_params(m) + flops.attention_flops(m, 600))


def test_codec_and_roofline():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    state = 2 * 671_088_640 + 4_194_304  # smollm k, v and pos leaves
    assert flops.codec_bytes(state) == state
    assert flops.least_seconds(0, state, peak) == state / 819e9
    assert flops.least_seconds(197e12, 1, peak) == 1.0
