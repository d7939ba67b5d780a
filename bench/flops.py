"""Operations and bytes that the served work needs, counted from shapes.

Each count is the work any implementation must do for the same answers:
it follows the model's equations and the positions actually attended,
not the kernels that happen to run, so a faster kernel lowers the time
but never the count. A forward pass of one token at position ``p`` is a
*pass*; the counters give, for a set of passes, their number ``n`` and
their summed context ``ctx = sum(p + 1)``.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(model: Dict) -> int:
    """Weights a token is multiplied by: attention projections, the MLP or
    its ``top_k`` experts and the router, and the tied output head over the
    published vocabulary. The embedding lookup multiplies nothing."""
    d, hd = model["hidden_size"], model["head_dim"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    ff = model["intermediate_size"]
    per_layer = 2 * d * H * hd + 2 * d * Hkv * hd
    E = model.get("num_local_experts", 0)
    if E:
        per_layer += d * E + model["num_experts_per_tok"] * 3 * d * ff
    else:
        per_layer += 3 * d * ff
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def attention_flops(model: Dict, ctx: int) -> int:
    """Scores and weighted values over every attended position: two
    multiply-adds per head dimension per head, per layer."""
    return (4 * model["num_hidden_layers"] * model["num_attention_heads"]
            * model["head_dim"] * ctx)


def attention_bytes(model: Dict, n: int, ctx: int, itemsize: int) -> int:
    """Decode attention reads the K and V of each attended position once and
    moves each pass's query and output once, in every layer."""
    L, hd = model["num_hidden_layers"], model["head_dim"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    return L * itemsize * (2 * Hkv * hd * ctx + 2 * H * hd * n)


def step_flops(model: Dict, n: int, ctx: int) -> int:
    """The whole forward work of ``n`` passes with summed context ``ctx``."""
    return 2 * matmul_params(model) * n + attention_flops(model, ctx)


def codec_bytes(state_bytes: int) -> int:
    """A pre-copy round must read the state it checkpoints at least once:
    every byte can have changed, and a fingerprint or a delta of it is a
    function of all of them."""
    return state_bytes


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
