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
