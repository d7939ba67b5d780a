"""Pallas TPU kernels for fused chunk fingerprint + delta encoding.

The checkpoint data path used to take *two* passes over each pre-copy
round's state: one device pass fingerprinting every chunk
(``kernels/fingerprint.py``) and, for the dirty set, a host pass feeding
the delta codecs in ``checkpoint/codecs.py``.  The kernels here fuse
dirty-detection and encoding into a single read of the state: one grid
step streams a chunk block through VMEM and emits

  * the chunk's fingerprint lanes (identical construction — and bit-exact
    results — to ``fingerprint._fp_kernel``), and
  * the codec's arithmetic core:
      - ``xor``  — the XOR of the chunk against its parent-image chunk
        (the run-length pass over that mostly-zero vector stays on host:
        it is O(dirty bytes) and variable-length, the wrong shape for a
        vector unit);
      - ``int8`` — blockwise symmetric int8 quantization of the float
        delta vs the decoded parent, exactly ``optim/compression._quant``:
        256-element blocks, ``scale = max(|delta|)/127`` clamped to 1e-12,
        round-half-even, clip to ±127.

Bit-exactness contract (the whole point of this module):

  * fingerprints equal ``ops.chunk_fingerprint`` exactly — same word
    layout, same uint32 arithmetic; trailing zero-row padding added for
    the int8 pair layout contributes ``weight * 0`` to every lane, so the
    padded and unpadded layouts agree;
  * the XOR output is exact by construction, so the host RLE pass over it
    yields bytes identical to ``XorRleCodec.encode``;
  * the quantizer emits the same ``(q, scale)`` as the host oracle: both
    are the same IEEE-754 f32 expression graph (sub, abs, max, div,
    round, clip), and max is order-insensitive, so the blockwise kernel,
    the jnp lowering and interpret mode agree bit-for-bit.  ``q`` leaves
    the kernel as int32 (TPU-friendly store) and is narrowed to int8 on
    host — values are already clipped to ±127.

Layouts mirror ``fingerprint.chunked_words``: ``[n_chunks, rows, 128]``
uint32 words on the registry's raw-byte chunk grid.  The int8 kernel
additionally needs an even row count per chunk (one 256-float quant block
spans two 128-word rows); ``pair_rows`` zero-pads one row when needed,
matching the host quantizer's zero-padding of the tail block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fingerprint import (
    LANES,
    LANES_SPEC,
    accumulate_lanes,
    as_i32,
    as_u32,
    fingerprint_lanes_ref,
    pad_rows,
    row_plan,
    row_weights_i32,
)

QBLOCK = 256                 # quant block length == optim.compression.BLOCK
_QROWS = QBLOCK // LANES     # word rows per quant block (= 2)


def pair_rows(words):
    """Zero-pad ``[C, R, 128]`` words to an even row count per chunk.

    Zero rows contribute ``weight * 0`` to every fingerprint lane and a
    zero delta to the tail quant block — exactly the host codec's
    zero-padding — so fingerprints and quantizer outputs are unchanged.
    """
    C, R, L = words.shape
    if R % _QROWS:
        words = jnp.pad(words, ((0, 0), (0, _QROWS - R % _QROWS), (0, 0)))
    return words


# ---------------------------------------------------------------------------
# fused fingerprint + XOR
# ---------------------------------------------------------------------------

def _xor_fp_kernel(cur_ref, par_ref, fp_ref, xor_ref, acc_ref, *,
                   block_rows: int, n_blocks: int):
    cur = cur_ref[...]
    xor_ref[...] = cur ^ par_ref[...]
    rows = (pl.program_id(1) * block_rows
            + jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0))
    accumulate_lanes(acc_ref, fp_ref, cur * row_weights_i32(rows), n_blocks)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def xor_fp_lanes(cur_words, par_words, *, block_rows: int = 256,
                 interpret: bool = False):
    """Fused pass: ``[C, R, 128]`` u32 x2 -> (fp lanes ``[C, 128]``,
    xor words ``[C, R, 128]``)."""
    C, R, L = cur_words.shape
    assert L == LANES and par_words.shape == cur_words.shape
    br, Rp = row_plan(R, block_rows)
    nb = Rp // br
    spec = pl.BlockSpec((None, br, LANES), lambda c, j: (c, j, 0))
    lanes, xor = pl.pallas_call(
        functools.partial(_xor_fp_kernel, block_rows=br, n_blocks=nb),
        grid=(C, nb),
        in_specs=[spec, spec],
        out_specs=[LANES_SPEC, spec],
        out_shape=[jax.ShapeDtypeStruct((C, 1, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((C, Rp, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(as_i32(pad_rows(cur_words, Rp)), as_i32(pad_rows(par_words, Rp)))
    return as_u32(lanes[:, 0]), as_u32(xor[:, :R])


def xor_fp_ref(cur_words, par_words):
    """Blockwise jnp formulation (CPU lowering of the fused kernel)."""
    return fingerprint_lanes_ref(cur_words), cur_words ^ par_words


# ---------------------------------------------------------------------------
# fused fingerprint + blockwise int8 quantization
# ---------------------------------------------------------------------------

def _quant_blocks(delta_blocks):
    """``optim.compression._quant`` core on ``[NB, 256]`` f32 blocks ->
    (q int32 ``[NB, 256]``, scale f32 ``[NB, 1]``).  The scale uses the
    same jit-stable reciprocal-multiply expression as the host quantizer
    (see ``optim.compression._INV127``) so eager host, interpret and
    compiled kernels agree bit-exactly."""
    from repro.optim.compression import _INV127

    scale = jnp.max(jnp.abs(delta_blocks), axis=1, keepdims=True) * _INV127
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(delta_blocks / scale), -127, 127)
    return q.astype(jnp.int32), scale.astype(jnp.float32)


def _column_to_row(col):
    """``[n, 1]`` -> ``[1, n]`` without a relayout: each lane of the row
    sums its diagonal entry and zeros, so the values are exact."""
    n = col.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _int8_fp_kernel(cur_ref, par_ref, fp_ref, q_ref, scale_ref, acc_ref, *,
                    block_qb: int, n_blocks: int):
    # one row per 256-float quant block: word row 2b in lanes [0, 128),
    # word row 2b + 1 in lanes [128, 256)
    cur = cur_ref[...]
    # quantize the float view; fingerprint the raw word view of the same
    # VMEM block — the fusion that saves the second pass over the state
    delta = (jax.lax.bitcast_convert_type(cur, jnp.float32)
             - jax.lax.bitcast_convert_type(par_ref[...], jnp.float32))
    q, scale = _quant_blocks(delta)
    q_ref[...] = q
    scale_ref[...] = _column_to_row(scale)
    shape = (block_qb, QBLOCK)
    rows = (_QROWS * (pl.program_id(1) * block_qb
                      + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1) // LANES)
    weighted = cur * row_weights_i32(rows)
    accumulate_lanes(acc_ref, fp_ref,
                     weighted[:, :LANES] + weighted[:, LANES:], n_blocks)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def int8_fp_lanes(cur_words, par_words, *, block_rows: int = 256,
                  interpret: bool = False):
    """Fused pass: ``[C, R, 128]`` u32 x2 (R even) -> (fp lanes
    ``[C, 128]``, q int32 ``[C, R//2, 256]``, scale f32 ``[C, R//2]``).

    The kernel sees each chunk as ``[R//2, 256]`` (one quant block per
    row, a free reshape) and writes the scales lane-dense as
    ``[C, 1, R//2]``; quant-block tiles are multiples of 128 so that both
    the q and the scale blocks meet the (8, 128) rule."""
    C, R, L = cur_words.shape
    assert L == LANES and R % _QROWS == 0, cur_words.shape
    assert par_words.shape == cur_words.shape
    NB = R // _QROWS
    bq, NBp = row_plan(NB, block_rows // _QROWS, tile=LANES)
    nb = NBp // bq

    def blocks(words):
        return as_i32(pad_rows(words.reshape(C, NB, QBLOCK), NBp))

    spec = pl.BlockSpec((None, bq, QBLOCK), lambda c, j: (c, j, 0))
    lanes, q, scale = pl.pallas_call(
        functools.partial(_int8_fp_kernel, block_qb=bq, n_blocks=nb),
        grid=(C, nb),
        in_specs=[spec, spec],
        out_specs=[
            LANES_SPEC,
            spec,
            pl.BlockSpec((None, 1, bq), lambda c, j: (c, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((C, NBp, QBLOCK), jnp.int32),
            jax.ShapeDtypeStruct((C, 1, NBp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(blocks(cur_words), blocks(par_words))
    return as_u32(lanes[:, 0]), q[:, :NB], scale[:, 0, :NB]


def int8_fp_ref(cur_words, par_words):
    """Blockwise jnp formulation (CPU lowering of the fused kernel)."""
    C, R, L = cur_words.shape
    assert R % _QROWS == 0, cur_words.shape
    delta = (jax.lax.bitcast_convert_type(cur_words, jnp.float32)
             - jax.lax.bitcast_convert_type(par_words, jnp.float32))
    q, scale = _quant_blocks(delta.reshape(C * R // _QROWS, QBLOCK))
    return (fingerprint_lanes_ref(cur_words),
            q.reshape(C, R // _QROWS, QBLOCK),
            scale.reshape(C, R // _QROWS))
