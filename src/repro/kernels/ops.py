"""Backend-dispatching jit'd wrappers around the Pallas kernels.

Models call these entry points only.  On TPU the Pallas kernels run; on CPU
(this container, incl. the 512-virtual-device dry-run) the blockwise jnp
formulations lower instead — chosen so the dry-run HLO's FLOP/byte profile
mirrors the kernel's tiling rather than a naive O(S^2)-materializing graph.

Set ``REPRO_FORCE_PALLAS_INTERPRET=1`` to route through the Pallas kernels in
interpret mode (slow; used by the kernel-equivalence tests).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout

from repro.kernels import ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import rglru as _rg


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_forced() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS_INTERPRET", "0") == "1"


def _fit_block(size: int, want: int) -> int:
    b = max(min(want, size), 1)
    while size % b:
        b //= 2
    return b


def attention(q, k, v, *, causal=True, window=0, block_k=1024):
    """Train/prefill attention.  q [B,S,H,D]; k/v [B,S,Hkv,D]."""
    if _on_tpu():
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=_fit_block(q.shape[1], 512),
            block_k=_fit_block(k.shape[1], 512))
    if _interpret_forced():
        Sq, Sk = q.shape[1], k.shape[1]
        bq = max(min(512, Sq), 1)
        bk = max(min(512, Sk), 1)
        while Sq % bq:
            bq //= 2
        while Sk % bk:
            bk //= 2
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=bq, block_k=bk, interpret=True,
        )
    if window and window > 0 and q.shape[1] == k.shape[1] and q.shape[1] % window == 0:
        return ref.banded_local_attention(q, k, v, window=window)
    return ref.blockwise_attention(q, k, v, causal=causal, window=window,
                                   block_k=block_k)


def _device():
    """The device the kernels compile for."""
    return jax.devices()[0]


@functools.lru_cache(maxsize=None)
def _seq_minor(shape, dtype, device) -> bool:
    """Whether ``device`` lays out a ``[..., S, Hkv, D]`` cache with S
    minor-most and whole heads in its tiles, as a TPU does for heads
    narrower than its 128 lanes: ``[..., Hkv*D, S]`` is then the same
    bytes."""
    layout = Layout.from_pjrt_layout(
        device.client.get_default_layout(np.dtype(dtype), shape, device))
    n = len(shape)
    rows = 1
    for tile in layout.tiling:
        rows *= tile[0] if len(tile) > 1 else 1
    return (tuple(layout.major_to_minor[-3:]) == (n - 2, n - 1, n - 3)
            and shape[-1] % rows == 0)


def decode_attention(q, k_cache, v_cache, q_pos, k_pos, layer=0):
    """Single-token attention over KV cache. q [B,1,H,D].

    The caches are ``[B,S,Hkv,D]``, or the stacked ``[L,B,S,Hkv,D]`` read at
    ``layer``.  The kernel reads a stack in place where the device keeps it
    seq-minor, and otherwise the layer sliced out (a relayout of one layer
    for its ``[S, Hkv*D]`` view); the jnp path slices the layer.
    """
    stacked = k_cache.ndim == 5
    if _on_tpu() or _interpret_forced():
        seq_minor = stacked and _seq_minor(k_cache.shape, k_cache.dtype,
                                           _device())
        if stacked and not seq_minor:
            k_cache = _at_layer(k_cache, layer)
            v_cache, layer = _at_layer(v_cache, layer), 0
        return _da.decode_attention(
            q, k_cache, v_cache, q_pos, k_pos, layer, seq_minor=seq_minor,
            block_k=_fit_block(k_cache.shape[-3], 512),
            interpret=not _on_tpu())
    if stacked:
        k_cache, v_cache = _at_layer(k_cache, layer), _at_layer(v_cache, layer)
    return ref.decode_attention(q, k_cache, v_cache, q_pos=q_pos, k_pos=k_pos)


def _at_layer(stack, layer):
    return jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False)


def moe_decode(x, gate_w, gate_ids, live, w_gate, w_up, w_down, layer=0):
    """Routed experts of a decode step.  x [B,D]; gate_w, gate_ids [B,K];
    live [B]; expert weights [E,...], or the stacked [L,E,...] read at
    ``layer`` -> (out [B,D] float32, fetches [B] int32: per lane, the
    expert weight fetches opened for it, the same on every path)."""
    from repro.kernels import moe_decode as _md

    if _on_tpu() or _interpret_forced():
        out = _md.moe_decode(x, gate_w, gate_ids, live, w_gate, w_up, w_down,
                             layer, interpret=not _on_tpu())
    else:
        out = ref.moe_decode(x, gate_w, gate_ids, live, w_gate, w_up, w_down,
                             layer)
    return out, _md.fetches(gate_ids, live)


def rglru_scan(x, a_param, gate_a, gate_x, h0=None, *, c: float = 8.0):
    """RG-LRU over a sequence. Returns (h_seq, h_last)."""
    if _on_tpu():
        W, S = x.shape[2], x.shape[1]
        bw = 512 if W % 512 == 0 else W
        ch = 256
        while S % ch:
            ch //= 2
        return _rg.rglru(x, a_param, gate_a, gate_x, h0, c=c, block_w=bw, chunk=ch)
    if _interpret_forced():
        W, S = x.shape[2], x.shape[1]
        ch = min(64, S)
        while S % ch:
            ch //= 2
        return _rg.rglru(x, a_param, gate_a, gate_x, h0, c=c, block_w=W,
                         chunk=ch, interpret=True)
    return ref.blockwise_rglru(x, a_param, gate_a, gate_x, h0, c=c)


def slstm_scan(x_i, x_f, x_z, x_o, r_i, r_f, r_z, r_o, state=None):
    """sLSTM over a sequence.  TPU (fresh state): per-head-parallel Pallas
    kernel; portable / state-threaded path: the lax.scan recurrence."""
    from repro.kernels import slstm as _sl

    if state is None and (_on_tpu() or _interpret_forced()):
        S = x_i.shape[1]
        ch = _fit_block(S, 128)
        h = _sl.slstm(x_i, x_f, x_z, x_o, r_i, r_f, r_z, r_o, chunk=ch,
                      interpret=not _on_tpu())
        return h, None
    return ref.naive_slstm(x_i, x_f, x_z, x_o, r_i, r_f, r_z, r_o, state)


def chunk_fingerprint(x, chunk_bytes: int):
    """Device-side chunk fingerprints of an array's raw bits.

    Returns ``[n_chunks, 4]`` uint32, one 128-bit fingerprint per
    ``chunk_bytes``-sized chunk of the flattened array (boundaries aligned
    with the checkpoint registry's raw-byte chunk grid).  Pre-copy dirty
    detection compares these instead of re-hashing full host buffers.
    """
    from repro.kernels import fingerprint as _fp

    words = _fp.chunked_words(x, chunk_bytes)
    if _on_tpu():
        lanes = _fp.fingerprint_lanes(words)
    elif _interpret_forced():
        lanes = _fp.fingerprint_lanes(words, interpret=True)
    else:
        lanes = _fp.fingerprint_lanes_ref(words)
    return _fp.collapse_lanes(lanes)


def _codec_words(cur, parent_u8, chunk_bytes: int, pair: bool):
    """Both sides of a fused codec pass in the kernel's word layout."""
    import numpy as np

    from repro.kernels import codec as _ck
    from repro.kernels import fingerprint as _fp

    words = _fp.chunked_words(cur, chunk_bytes)
    pwords = _fp.chunked_words(np.frombuffer(parent_u8, np.uint8),
                               chunk_bytes)
    assert pwords.shape == words.shape, (words.shape, pwords.shape)
    if pair:
        words, pwords = _ck.pair_rows(words), _ck.pair_rows(pwords)
    return words, pwords


def fused_xor_fingerprint(cur, parent_raw: bytes, chunk_bytes: int):
    """One fused pass over ``cur``: chunk fingerprints + XOR vs parent.

    Returns ``(fps [C, 4] u32, xor_words [C, R, 128] u32)``.  The
    fingerprints are bit-identical to ``chunk_fingerprint(cur, ...)``;
    the XOR words feed the host RLE pass of the ``xor_rle`` codec, whose
    output is byte-identical to the host codec's.
    """
    from repro.kernels import codec as _ck
    from repro.kernels import fingerprint as _fp

    words, pwords = _codec_words(cur, parent_raw, chunk_bytes, pair=False)
    if _on_tpu():
        lanes, xor = _ck.xor_fp_lanes(words, pwords)
    elif _interpret_forced():
        lanes, xor = _ck.xor_fp_lanes(words, pwords, interpret=True)
    else:
        lanes, xor = _ck.xor_fp_ref(words, pwords)
    return _fp.collapse_lanes(lanes), xor


def fused_int8_fingerprint(cur, parent_raw: bytes, chunk_bytes: int):
    """One fused pass over ``cur``: chunk fingerprints + blockwise int8
    quantization of the f32 delta vs the decoded parent.

    Returns ``(fps [C, 4] u32, q int32 [C, NB, 256], scale f32 [C, NB])``
    with ``NB`` quant blocks per (zero-padded) chunk; ``q``/``scale``
    match ``optim.compression._quant`` on each chunk's delta bit-exactly.
    """
    from repro.kernels import codec as _ck
    from repro.kernels import fingerprint as _fp

    words, pwords = _codec_words(cur, parent_raw, chunk_bytes, pair=True)
    if _on_tpu():
        lanes, q, scale = _ck.int8_fp_lanes(words, pwords)
    elif _interpret_forced():
        lanes, q, scale = _ck.int8_fp_lanes(words, pwords, interpret=True)
    else:
        lanes, q, scale = _ck.int8_fp_ref(words, pwords)
    return _fp.collapse_lanes(lanes), q, scale


def mlstm_scan(q, k, v, i_gate, f_gate, state=None):
    """mLSTM over a sequence.  TPU: chunkwise-parallel Pallas kernel (MXU
    matmuls); portable path: the stabilized lax.scan recurrence.

    The Pallas path currently returns outputs only (fresh-state sequences,
    as in training); callers threading serving state use the scan path.
    """
    from repro.kernels import mlstm as _ml

    if state is None and _on_tpu():
        S = q.shape[1]
        ch = 128
        while S % ch:
            ch //= 2
        h = _ml.mlstm(q, k, v, i_gate, f_gate, chunk=ch)
        # final state for cache continuation comes from the scan path only
        # when requested; training uses h alone.
        return h, None
    if state is None and _interpret_forced():
        S = q.shape[1]
        ch = min(64, S)
        while S % ch:
            ch //= 2
        h = _ml.mlstm(q, k, v, i_gate, f_gate, chunk=ch, interpret=True)
        return h, None
    return ref.naive_mlstm(q, k, v, i_gate, f_gate, state)
