"""Device-side chunk fingerprinting: Pallas (interpret mode) vs the
blockwise jnp lowering, bit-exactly, plus the dirty-detection semantics
the registry relies on."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import fingerprint as fp
from repro.kernels import ops

CB = 64 * 1024  # chunk bytes used throughout


def _fps_ref(x, chunk_bytes=CB):
    words = fp.chunked_words(x, chunk_bytes)
    return np.asarray(fp.collapse_lanes(fp.fingerprint_lanes_ref(words)))


@pytest.mark.parametrize("n,dtype", [
    (300_000, np.float32),     # multi-chunk, word-sized elements
    (50_000, np.float64),      # 8-byte elements
    (123_456, np.int8),        # sub-word elements, odd tail
    (77_777, np.uint16),       # 2-byte grouping, odd tail
    (100, np.float32),         # single chunk, sub-row leaf
])
def test_interpret_matches_jnp_lowering(n, dtype):
    rng = np.random.default_rng(0)
    x = rng.integers(0, 127, n).astype(dtype)
    words = fp.chunked_words(x, CB)
    ref = fp.collapse_lanes(fp.fingerprint_lanes_ref(words))
    pal = fp.collapse_lanes(fp.fingerprint_lanes(words, interpret=True))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


def test_force_interpret_env_routes_through_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    x = np.arange(40_000, dtype=np.float32)
    via_pallas = np.asarray(ops.chunk_fingerprint(x, CB))
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "0")
    via_jnp = np.asarray(ops.chunk_fingerprint(x, CB))
    np.testing.assert_array_equal(via_pallas, via_jnp)


def test_chunk_grid_matches_registry_chunk_count():
    for nbytes in (1, CB - 4, CB, CB + 4, 3 * CB + 100):
        n = nbytes // 4
        if n == 0:
            continue
        x = np.zeros(n, np.float32)
        want_chunks = -(-x.nbytes // CB)
        assert _fps_ref(x).shape == (want_chunks, fp.FP_WORDS)


def test_single_element_change_dirties_only_its_chunk():
    x = np.zeros(10 * CB // 4, np.float32)
    base = _fps_ref(x)
    for chunk in (0, 4, 9):
        y = x.copy()
        y[chunk * (CB // 4) + 17] = 1.0
        diff = (base != _fps_ref(y)).any(axis=1)
        assert list(np.flatnonzero(diff)) == [chunk]


def test_equal_content_equal_fingerprint_across_positions():
    """Content addressing: a chunk's fingerprint depends on its content
    only, not on which chunk slot it occupies."""
    pattern = np.arange(CB // 4, dtype=np.float32)
    x = np.concatenate([pattern, np.zeros(CB // 4, np.float32), pattern])
    fps = _fps_ref(x)
    np.testing.assert_array_equal(fps[0], fps[2])
    assert (fps[0] != fps[1]).any()


def test_order_sensitivity_within_chunk():
    x = np.arange(CB // 4, dtype=np.float32)
    y = x.copy()
    y[1000], y[2000] = y[2000], y[1000]  # swap two unequal elements
    assert (_fps_ref(x) != _fps_ref(y)).any()


def test_bit_reinterpretation_not_value_hash():
    """-0.0 == 0.0 numerically but differs bitwise: the fingerprint must
    see bits (the registry chunks raw bytes)."""
    x = np.zeros(1024, np.float32)
    y = x.copy()
    y[3] = -0.0
    assert (_fps_ref(x) != _fps_ref(y)).any()


def test_jax_and_numpy_inputs_agree():
    x = np.random.default_rng(1).standard_normal(30_000).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.chunk_fingerprint(x, CB)),
        np.asarray(ops.chunk_fingerprint(jnp.asarray(x), CB)))


def test_bfloat16_words():
    x = jnp.arange(5000, dtype=jnp.bfloat16)
    out = np.asarray(ops.chunk_fingerprint(x, CB))
    assert out.shape == (1, fp.FP_WORDS)
    y = jnp.concatenate([x[:100] + 1, x[100:]])
    assert (np.asarray(ops.chunk_fingerprint(y, CB)) != out).any()


@pytest.mark.parametrize("n", [1, 2, 7, 4096, 100_003])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.uint16, jnp.float16,
                                   jnp.int8, jnp.bool_])
def test_device_words_equal_the_host_byte_view(dtype, n):
    """A device leaf's words are its bytes read as little-endian uint32,
    the tail zero-padded: the words a host leaf gives, so chunk
    fingerprints and image ids do not depend on where the leaf lives."""
    rng = np.random.default_rng(n)
    bits = rng.integers(0, 1 << 16, n, dtype=np.uint32).astype(np.uint16)
    host = (bits.view(jnp.dtype(dtype)) if jnp.dtype(dtype).itemsize == 2
            else (bits % 2 if dtype == jnp.bool_ else bits % 256).astype(
                jnp.dtype(dtype)))
    got = np.asarray(fp.as_u32_words(jnp.asarray(host)))
    np.testing.assert_array_equal(got, np.asarray(fp.as_u32_words(host)))
    b = host.view(np.uint8)
    b = np.concatenate([b, np.zeros(-b.size % 4, np.uint8)])
    np.testing.assert_array_equal(got, b.view(np.uint32))
