"""The main path's Pallas kernels compiled for a described TPU v5e chip.

The TPU compiler is installed next to JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes that break the (8, 128) tiling rule, reductions Mosaic does
not implement, layouts it cannot lower.  The shapes are those
``chip_smoke.py`` runs on the chip.  Nothing runs: a compile that passes
here says nothing about results or times.

This is the only test file that describes the chip.  The TPU library may
be loaded by one process at a time, so the topology is described inside a
fixture (never while a module is imported) and every compile runs in the
test's own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import codec as ck
from repro.kernels import decode_attention as da
from repro.kernels import fingerprint as fp
from repro.kernels import flash_attention as fa
from repro.kernels import moe_decode as md


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it out
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _codec_case(kernel, n_inputs):
    def lower(shape, sds):
        words = sds(shape, jnp.uint32)
        return kernel.lower(*[words] * n_inputs)
    return lower


def _int8_case(shape, sds):
    C, R, L = shape
    words = sds((C, R + R % 2, L), jnp.uint32)   # ck.pair_rows
    return ck.int8_fp_lanes.lower(words, words)


def _decode_case(shape, sds):
    B, S, H, Hkv, D, dtype = shape
    return da.decode_attention.lower(
        sds((B, 1, H, D), dtype), sds((B, S, Hkv, D), dtype),
        sds((B, S, Hkv, D), dtype), sds((B,), jnp.int32),
        sds((B, S), jnp.int32))


def _stacked_decode_case(shape, sds):
    L, B, S, H, Hkv, D, dtype = shape
    return da.decode_attention.lower(
        sds((B, 1, H, D), dtype), sds((L, B, S, Hkv, D), dtype),
        sds((L, B, S, Hkv, D), dtype), sds((B,), jnp.int32),
        sds((B, S), jnp.int32), sds((), jnp.int32), seq_minor=True)


def _moe_decode_case(shape, sds):
    L, E, B, K, D, F, dtype = shape
    return md.moe_decode.lower(
        sds((B, D), dtype), sds((B, K), jnp.float32), sds((B, K), jnp.int32),
        sds((B,), jnp.bool_), sds((L, E, D, F), dtype),
        sds((L, E, D, F), dtype), sds((L, E, F, D), dtype),
        sds((), jnp.int32))


def _flash_case(shape, sds):
    B, S, H, Hkv, D, dtype = shape
    return fa.flash_attention.lower(
        sds((B, S, H, D), dtype), sds((B, S, Hkv, D), dtype),
        sds((B, S, Hkv, D), dtype), causal=True, block_q=S, block_k=S)


# the registry's two chunk grids, as [C, R, 128] words: 512-byte chunks
# over a serving engine's KV leaf, and one whole 1 MiB leaf (the fold
# consumer's KV cache at max_seq=2048)
SLOT_GRID = (1024, 1, 128)
LEAF_GRID = (1, 2048, 128)

CASES = {
    "fingerprint_lanes-slot_grid": (_codec_case(fp.fingerprint_lanes, 1),
                                    SLOT_GRID),
    "fingerprint_lanes-leaf_grid": (_codec_case(fp.fingerprint_lanes, 1),
                                    LEAF_GRID),
    "xor_fp_lanes-slot_grid": (_codec_case(ck.xor_fp_lanes, 2), SLOT_GRID),
    "xor_fp_lanes-leaf_grid": (_codec_case(ck.xor_fp_lanes, 2), LEAF_GRID),
    "int8_fp_lanes-slot_grid": (_int8_case, SLOT_GRID),
    "int8_fp_lanes-leaf_grid": (_int8_case, LEAF_GRID),
    # paper_consumer's serving engine: 8 slots over a 128-position cache
    "decode_attention-paper_consumer_engine": (
        _decode_case, (8, 128, 8, 4, 32, jnp.float32)),
    # the fold consumer: one row over 2048 positions
    "decode_attention-paper_consumer_fold": (
        _decode_case, (1, 2048, 8, 4, 32, jnp.float32)),
    # smollm_360m serving: 15 query heads over 5 kv heads of 64, bf16
    "decode_attention-smollm_360m": (
        _decode_case, (8, 128, 15, 5, 64, jnp.bfloat16)),
    # the engine's step: one layer of smollm_360m's stacked cache, read
    # in place in the layout the chip keeps it (3 slots over 2048)
    "decode_attention-smollm_360m_stack": (
        _stacked_decode_case, (32, 3, 2048, 15, 5, 64, jnp.bfloat16)),
    # the engine's step: granite_moe_1b_a400m's routed experts, one layer
    # of the stacked [24, 32, ...] expert weights read in place, 3 lanes
    "moe_decode-granite_moe_1b_a400m_stack": (
        _moe_decode_case, (24, 32, 3, 8, 1024, 512, jnp.bfloat16)),
    "flash_attention-smollm_360m_prefill": (
        _flash_case, (8, 32, 15, 5, 64, jnp.bfloat16)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    lower, shape = CASES[case]

    def sds(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    compiled = lower(shape, sds).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_copies_no_layer_of_the_cache(topo, one_chip,
                                                 monkeypatch):
    """The engine's decode step for smollm_360m at 3 slots over 2048
    positions, compiled for the chip: its cache is donated and updated in
    place, and the kernel reads each layer where it lies, so no copy moves
    a whole layer's keys or values."""
    import functools
    import math
    import re

    from repro import configs
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.serving import engine

    # the dispatch asks the default backend (the CPU here) for the chip
    # and its layouts: steer both to the described chip
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_device", lambda: topo.devices[0])
    cfg = configs.get_config("smollm_360m")
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16
                                             if a.dtype == jnp.float32
                                             else a.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(T.init_lm, jax.random.PRNGKey(0), cfg)))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(T.init_cache, cfg, 3, 2048)))
    host = jax.ShapeDtypeStruct((3, 3), jnp.int32, sharding=one_chip)
    last = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
    step = engine._owned_step(engine._decode_all)
    compiled = step.lower(params, cfg, cache, host, last).compile()
    text = compiled.as_text()
    layer = 3 * 2048 * cfg.num_kv_heads * cfg.resolved_head_dim
    copies = [[int(d) for d in dims.split(",") if d] for dims in re.findall(
        r"= [a-z0-9]+\[([\d,]*)\]\S* copy\(", text)]
    # a copy over the cache's positions as large as one layer's keys
    of_cache = [c for c in copies if 2048 in c and math.prod(c) >= layer]
    assert copies and not of_cache, of_cache
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))


def test_fingerprint_and_xor_of_a_300_mb_leaf_fit(one_chip):
    """One granite_moe_1b_a400m KV leaf, bf16[24,3,4096,8,64] (302 MB), on
    the registry's 384 KiB grid: its words, fingerprints and XOR against a
    parent compile for the chip in at most 3x the leaf of temporaries. A
    bitcast of a [n, 2] uint16 view, whose minor dimension the chip pads to
    128 lanes, asked for 64x (19.3 GB)."""
    import math

    shape, chunk = (24, 3, 4096, 8, 64), 384 * 1024
    leaf = 2 * math.prod(shape)
    words = (leaf // chunk, chunk // (4 * fp.LANES), fp.LANES)

    def push(x, parent_words):
        lanes, xor = ck.xor_fp_lanes(fp.chunked_words(x, chunk),
                                     parent_words)
        return fp.collapse_lanes(lanes), xor

    compiled = jax.jit(push).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct(words, jnp.uint32, sharding=one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 3 * leaf


def test_moe_decode_step_copies_no_expert(topo, one_chip, monkeypatch):
    """The engine's decode step for granite_moe_1b_a400m at 3 slots over
    4096 positions, compiled for the chip: the routed-expert kernel reads
    each layer's experts from the stacked weights where they lie, so no
    layer's experts are sliced out or copied, and the cache is updated in
    place."""
    import functools
    import re

    from repro import configs
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.serving import engine

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_device", lambda: topo.devices[0])
    cfg = configs.get_config("granite_moe_1b_a400m")
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16
                                             if a.dtype == jnp.float32
                                             else a.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(T.init_lm, jax.random.PRNGKey(0), cfg)))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(T.init_cache, cfg, 3, 4096)))
    host = jax.ShapeDtypeStruct((3, 3), jnp.int32, sharding=one_chip)
    last = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
    step = engine._owned_step(engine._decode_all)
    compiled = step.lower(params, cfg, cache, host, last).compile()
    text = compiled.as_text()
    L, E, d, ff = cfg.num_layers, cfg.num_experts, cfg.d_model, cfg.d_ff
    kernel = [ln for ln in text.splitlines()
              if ln.strip().startswith("%moe_decode")]
    # its operands are the stacks themselves, not a layer sliced out
    stack = f"bf16[{L},{E},{d},{ff}]", f"bf16[{L},{E},{ff},{d}]"
    assert kernel and all(ln.count(stack[0]) == 2 and stack[1] in ln
                          for ln in kernel), kernel
    # and no array in the program holds one layer's experts
    layer = re.compile(rf"\[(1,)?{E},({d},{ff}|{ff},{d})\]")
    assert not layer.search(text)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
