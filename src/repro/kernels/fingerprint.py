"""Pallas TPU kernel for device-side chunk fingerprinting.

The checkpoint registry content-addresses chunks by sha256 of their bytes,
which forces every pre-copy round to serialize each leaf to host memory and
re-hash it — even when almost nothing changed.  This kernel reduces each
registry chunk to a 128-bit fingerprint *on device* in one fused streaming
pass, so dirty detection between consecutive checkpoints becomes a
fingerprint comparison: only chunks whose fingerprint changed are
serialized, encoded and hashed on host.

Construction (all arithmetic uint32, wrap-around mod 2^32, so the Pallas
kernel, the blockwise jnp lowering and interpret mode agree bit-exactly;
the kernel carries the same bits as int32, see ``_mix32_i32``):

  * a leaf's raw bytes are reinterpreted as uint32 words and laid out as
    ``[n_chunks, rows, 128]`` (128 = TPU lane width; rows stream through
    VMEM in blocks);
  * stage 1 (the kernel): per chunk, each lane accumulates a weighted sum
    over rows, ``lane[j] = sum_r mix32(r) * w[r, j]`` — weights depend on
    the intra-chunk row index only, so equal content yields equal
    fingerprints regardless of chunk position (matching content
    addressing), while any positional move *within* a chunk changes it;
  * stage 2 (negligible, shared jnp): the 128 lanes collapse to
    ``FP_WORDS`` words under four independently seeded weightings.

A fingerprint collision would silently drop a dirty chunk, so the collapse
keeps 4 x 32 bits; every migration path additionally verifies the restored
state against a reference fold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # TPU lane width; stage-1 fingerprint width
FP_WORDS = 4         # final fingerprint words per chunk (4 x u32 = 128 bit)
_GOLD = 0x9E3779B1   # 2^32 / golden ratio (Weyl constant)
_COLLAPSE_SEEDS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)


def _mix32(x):
    """murmur3-style uint32 finalizer (elementwise, VPU-friendly)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _i32(c: int) -> int:
    """A uint32 constant as the int32 with the same bits."""
    return c - (1 << 32) if c >= (1 << 31) else c


def _mix32_i32(x):
    """``_mix32`` on the int32 view of the same bits.  Mosaic implements
    no reductions over unsigned integers, so the kernels carry every word
    as int32: add, multiply and xor wrap to the same 32 bits, and the
    shifts are logical, so results bitcast back to exactly ``_mix32``'s."""
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, jnp.int32(16))
    x = x * jnp.int32(_i32(0x7FEB352D))
    x = x ^ srl(x, jnp.int32(15))
    x = x * jnp.int32(_i32(0x846CA68B))
    x = x ^ srl(x, jnp.int32(16))
    return x


def row_weights_i32(rows):
    """Per-row odd weights (int32 bits) for absolute 0-based row ids."""
    r = (rows + 1) * jnp.int32(_i32(_GOLD))
    return _mix32_i32(r) | jnp.int32(1)


def row_plan(rows: int, want: int, tile: int = 8):
    """-> (block_rows, padded_rows) for a row axis streamed through VMEM.

    A block is the whole axis when it fits in ``want`` rows; otherwise a
    multiple of ``tile`` (the TPU's (8, 128) block rule) that divides the
    axis once it is zero-padded up to the tile.  Zero rows add
    ``weight * 0`` to every fingerprint lane, so padding never changes a
    fingerprint; callers slice padded outputs away."""
    if rows <= want:
        return rows, rows
    padded = -(-rows // tile) * tile
    block = max(tile, want - want % tile)
    while padded % block:
        block -= tile
    return block, padded


def as_i32(words):
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def as_u32(words):
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def pad_rows(words, padded: int):
    R = words.shape[1]
    if padded == R:
        return words
    return jnp.pad(words, ((0, 0), (0, padded - R), (0, 0)))


def accumulate_lanes(acc_ref, out_ref, weighted, n_blocks: int):
    """Add a block's row sums into the lane accumulator; emit on the last
    row block of the chunk."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(weighted, axis=0, keepdims=True)

    @pl.when(j == n_blocks - 1)
    def _done():
        out_ref[...] = acc_ref[...]


def _fp_kernel(w_ref, out_ref, acc_ref, *, block_rows: int, n_blocks: int):
    rows = (pl.program_id(1) * block_rows
            + jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0))
    accumulate_lanes(acc_ref, out_ref, w_ref[...] * row_weights_i32(rows),
                     n_blocks)


LANES_SPEC = pl.BlockSpec((None, 1, LANES), lambda c, j: (c, 0, 0))
"""One chunk's ``[1, 128]`` lane row of a ``[C, 1, 128]`` output: the
last two block dims equal the array's, which the TPU's block rule
accepts for any chunk count."""


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fingerprint_lanes(words, *, block_rows: int = 256,
                      interpret: bool = False):
    """Stage 1 on Pallas: ``[C, R, 128]`` uint32 -> ``[C, 128]`` uint32.

    Grid (chunk, row block); a chunk's rows stream through VMEM in blocks
    of ``row_plan(R, block_rows)`` rows."""
    C, R, L = words.shape
    assert L == LANES, words.shape
    br, Rp = row_plan(R, block_rows)
    nb = Rp // br
    lanes = pl.pallas_call(
        functools.partial(_fp_kernel, block_rows=br, n_blocks=nb),
        grid=(C, nb),
        in_specs=[pl.BlockSpec((None, br, LANES), lambda c, j: (c, j, 0))],
        out_specs=LANES_SPEC,
        out_shape=jax.ShapeDtypeStruct((C, 1, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(as_i32(pad_rows(words, Rp)))
    return as_u32(lanes[:, 0])


def fingerprint_lanes_ref(words):
    """Stage 1, blockwise jnp formulation (CPU lowering of the kernel)."""
    C, R, L = words.shape
    assert L == LANES, words.shape
    r = jnp.arange(R, dtype=jnp.uint32) + jnp.uint32(1)
    w = _mix32(r * jnp.uint32(_GOLD)) | jnp.uint32(1)
    return jnp.sum(words * w[None, :, None], axis=1, dtype=jnp.uint32)


def collapse_lanes(lanes):
    """Stage 2 (shared): ``[C, 128]`` uint32 -> ``[C, FP_WORDS]`` uint32."""
    j = jnp.arange(LANES, dtype=jnp.uint32) + jnp.uint32(1)
    w = jnp.stack([_mix32(j * jnp.uint32(s)) | jnp.uint32(1)
                   for s in _COLLAPSE_SEEDS])          # [FP_WORDS, 128]
    return jnp.sum(lanes[:, None, :] * w[None, :, :], axis=-1,
                   dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Byte-layout helpers: raw array bits -> the kernel's [C, R, 128] layout
# ---------------------------------------------------------------------------

def as_u32_words(x):
    """Bit-reinterpret an array as a flat uint32 word vector (device-side
    for jax arrays; zero-pads the tail to a 4-byte boundary)."""
    import numpy as np

    if not isinstance(x, jax.Array):
        # numpy leaves go through a host byte view: jnp.asarray would
        # silently downcast 64-bit dtypes (x64 disabled) and desync the
        # fingerprint chunk grid from the registry's raw-byte grid
        b = np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)
        pad = (-b.size) % 4
        if pad:
            b = np.concatenate([b, np.zeros(pad, np.uint8)])
        return jnp.asarray(b.view(np.uint32))
    return _device_words(x)


@jax.jit
def _device_words(x):
    """``as_u32_words`` of a device array, as one program.

    Elements narrower than a word are packed little-endian, ``group`` to a
    word, by shifts over strided slices of the flat vector. A bitcast of a
    ``[n, group]`` view gives the same words, but a TPU pads that view's
    minor dimension to its 128 lanes: 64x the leaf for 2-byte elements,
    more than the chip holds for a 300-MB leaf. No intermediate here has
    a minor dimension narrower than the leaf's own."""
    x = x.reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    isz = x.dtype.itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if isz == 8:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    group = 4 // isz  # 2-byte or 1-byte elements: group into one word
    # integers from here on: a float pad or copy may quiet a NaN's payload
    x = jax.lax.bitcast_convert_type(x, jnp.uint16 if isz == 2 else jnp.uint8)
    pad = (-x.size) % group
    if pad:
        x = jnp.pad(x, (0, pad))
    part = lambda j: jax.lax.slice(x, (j,), (x.size,), (group,)).astype(
        jnp.uint32) << jnp.uint32(8 * isz * j)
    return functools.reduce(jnp.bitwise_or, map(part, range(group)))


def chunked_words(x, chunk_bytes: int):
    """-> uint32 words of ``x`` arranged ``[n_chunks, rows, 128]``, chunk
    boundaries aligned with the registry's raw-byte chunk grid (requires
    ``chunk_bytes`` to be a positive multiple of 512)."""
    assert chunk_bytes >= 4 * LANES and chunk_bytes % (4 * LANES) == 0, \
        chunk_bytes
    words = as_u32_words(x)
    wpc = chunk_bytes // 4
    n = words.size
    if n <= wpc:
        # single-chunk leaf: pad only to the lane grid, not the full chunk
        wpc = max(LANES, ((n + LANES - 1) // LANES) * LANES)
    n_chunks = max(1, -(-n // wpc))
    pad = n_chunks * wpc - n
    if pad:
        words = jnp.pad(words, (0, pad))
    return words.reshape(n_chunks, wpc // LANES, LANES)
