"""Pluggable delta codecs for registry chunks.

A codec transforms one chunk's *wire/stored* representation; the registry
records the codec per chunk in the manifest so pulls can invert it.  Two
families:

  * ``none``     — identity (the only choice for parentless chunks).
  * ``xor_rle``  — XOR against the parent image's chunk at the same
    position, then byte-level run-length coding of the zero runs.
    Lossless.  Near-static chunks (weight layers, cold cache regions)
    collapse to a few bytes; a chunk with a small dirty stripe costs the
    stripe, not the chunk.
  * ``int8``     — blockwise int8 quantization of the float delta
    ``chunk - decode(parent chunk)``, reusing the error-feedback quantizer
    from ``optim/compression.py``.  LOSSY per round: the quantization
    error is *not* dropped but carried forward, because the next round's
    delta is computed against the receiver's lossy reconstruction (the
    decoded parent chain) — exactly the EF21-style y-tracking trick.  The
    pre-copy transfer engine finishes a lossy lineage with one lossless
    "exact flush" push, so the image actually restored at cutover — and
    therefore the replayed state — stays bit-exact.

Codec choice is per leaf: ``resolve_compression`` maps the
``MigrationPolicy.compression`` knob (a codec name, ``"auto"``, or a
``{tree name: codec}`` dict) to a concrete codec given the leaf's dtype,
whether a compatible parent chunk exists, and whether a lossy encoding is
acceptable for this push.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Union

import numpy as np

from repro import obs

COMPRESSION_CHOICES = ("none", "xor_rle", "int8", "auto")

# Encoder backend for parent-relative codecs.  "kernel" (the default)
# lets the registry fuse fingerprinting and encoding into one device pass
# (repro.kernels.codec) when the leaf/chunk grid qualifies; "host" forces
# the two-pass host codecs below — the differential suite runs both and
# asserts byte-identical images.
_BACKEND_ENV = "REPRO_CODEC_BACKEND"


def codec_backend() -> str:
    backend = os.environ.get(_BACKEND_ENV, "kernel")
    if backend not in ("kernel", "host"):
        raise ValueError(
            f"{_BACKEND_ENV}={backend!r}; choices: ('kernel', 'host')")
    return backend

_RAW_FLAG = b"\x00"   # xor_rle fallback: raw literal chunk follows
_RLE_FLAG = b"\x01"   # xor_rle: run-length stream follows

_FLOAT_KINDS = ("f",)  # np dtype kinds the int8 codec quantizes


def _rle_encode(x: np.ndarray) -> bytes:
    """Byte-level RLE of a mostly-zero uint8 vector.

    Stream of ``(u32 zero_run, u32 lit_len, lit bytes)`` tokens; built
    from the nonzero index set with numpy, so near-static chunks encode in
    O(dirty) not O(chunk).
    """
    nz = np.flatnonzero(x)
    out = []
    if nz.size == 0:
        return b""
    # group nonzero indices into literal segments, absorbing zero gaps
    # shorter than the 8-byte token header (splitting there costs more)
    breaks = np.flatnonzero(np.diff(nz) > 16) + 1
    starts = np.concatenate(([0], breaks))
    ends = np.concatenate((breaks, [nz.size]))
    pos = 0
    for s, e in zip(starts, ends):
        lo, hi = int(nz[s]), int(nz[e - 1]) + 1
        out.append(int(lo - pos).to_bytes(4, "little"))
        out.append(int(hi - lo).to_bytes(4, "little"))
        out.append(x[lo:hi].tobytes())
        pos = hi
    return b"".join(out)


def _rle_decode(blob: bytes, n: int) -> np.ndarray:
    out = np.zeros(n, dtype=np.uint8)
    pos = off = 0
    view = memoryview(blob)
    while off < len(view):
        zrun = int.from_bytes(view[off: off + 4], "little")
        lit = int.from_bytes(view[off + 4: off + 8], "little")
        off += 8
        pos += zrun
        out[pos: pos + lit] = np.frombuffer(view[off: off + lit], np.uint8)
        pos += lit
        off += lit
    return out


class DeltaCodec:
    name: str = "?"
    lossless: bool = True

    def encode(self, raw: bytes, parent_raw: Optional[bytes],
               dtype: np.dtype) -> bytes:
        raise NotImplementedError

    def decode(self, blob: bytes, parent_raw: Optional[bytes],
               dtype: np.dtype) -> bytes:
        raise NotImplementedError


class NoneCodec(DeltaCodec):
    name = "none"

    def encode(self, raw, parent_raw, dtype):
        return raw

    def decode(self, blob, parent_raw, dtype):
        return blob


class XorRleCodec(DeltaCodec):
    name = "xor_rle"

    def encode(self, raw, parent_raw, dtype):
        assert parent_raw is not None and len(parent_raw) == len(raw)
        x = np.frombuffer(raw, np.uint8) ^ np.frombuffer(parent_raw, np.uint8)
        rle = _rle_encode(x)
        if len(rle) + 1 >= len(raw):  # incompressible: never exceed raw+1
            return _RAW_FLAG + raw
        return _RLE_FLAG + rle

    def decode(self, blob, parent_raw, dtype):
        if blob[:1] == _RAW_FLAG:
            return blob[1:]
        assert parent_raw is not None
        x = _rle_decode(blob[1:], len(parent_raw))
        return (x ^ np.frombuffer(parent_raw, np.uint8)).tobytes()


class Int8DeltaCodec(DeltaCodec):
    """Blockwise-int8 quantized float delta vs the decoded parent chunk
    (see module docstring for the error-feedback/exact-flush contract)."""

    name = "int8"
    lossless = False

    def encode(self, raw, parent_raw, dtype):
        from repro.optim.compression import _quant

        assert parent_raw is not None and len(parent_raw) == len(raw)
        cur = np.frombuffer(raw, dtype).astype(np.float32)
        par = np.frombuffer(parent_raw, dtype).astype(np.float32)
        q, scale, _, pad = _quant(cur - par)
        q, scale = np.asarray(q), np.asarray(scale)
        header = (int(pad).to_bytes(4, "little")
                  + int(q.size).to_bytes(4, "little"))
        return header + q.tobytes() + scale.tobytes()

    def decode(self, blob, parent_raw, dtype):
        from repro.optim.compression import BLOCK, _dequant

        assert parent_raw is not None
        pad = int.from_bytes(blob[:4], "little")
        nq = int.from_bytes(blob[4:8], "little")
        q = np.frombuffer(blob[8: 8 + nq], np.int8).reshape(-1, BLOCK)
        scale = np.frombuffer(blob[8 + nq:], np.float32).reshape(-1, 1)
        par = np.frombuffer(parent_raw, dtype).astype(np.float32)
        delta = np.asarray(_dequant(q, scale, (par.size,), pad))
        return (par + delta).astype(dtype).tobytes()


CODECS: Dict[str, DeltaCodec] = {
    c.name: c for c in (NoneCodec(), XorRleCodec(), Int8DeltaCodec())
}


def get_codec(name: str) -> DeltaCodec:
    codec = CODECS.get(name)
    if codec is None:
        raise ValueError(
            f"unknown codec {name!r}; concrete codecs: {tuple(CODECS)} "
            "(specs like 'auto' must go through resolve_compression first)")
    return codec


def validate_compression(spec: Union[str, Dict[str, str]]) -> None:
    specs = spec.values() if isinstance(spec, dict) else (spec,)
    for s in specs:
        if s not in COMPRESSION_CHOICES:
            raise ValueError(
                f"unknown compression codec {s!r}; "
                f"choices: {COMPRESSION_CHOICES}")


def resolve_compression(spec: Union[str, Dict[str, str]], tree_name: str,
                        dtype: np.dtype, has_parent_chunk: bool,
                        lossy_ok: bool, chunk_bytes: int = 0) -> str:
    """Pick the concrete codec for one leaf's chunks.

    Note the cluster migration path pushes a single tree named
    ``"state"``; dict specs keyed by other tree names only take effect
    for direct multi-tree ``Registry`` pushes.
    """
    if isinstance(spec, dict):
        spec = spec.get(tree_name, "none")
    # re-check the *resolved* entry: a caller that skipped
    # validate_compression (or a dict naming an unknown codec for this
    # very tree) must fail here with ValueError, not silently map to a
    # fallback codec or KeyError later at push time
    validate_compression(spec)
    if spec == "none" or not has_parent_chunk:
        return "none"
    if spec == "int8":
        # the lossy quantizer only applies to float leaves on non-final
        # pushes, and needs chunk boundaries on the dtype's element grid
        # (an unaligned chunk_bytes would split an element across chunks);
        # everything else falls back to the lossless delta codec
        dt = np.dtype(dtype)
        if (lossy_ok and dt.kind in _FLOAT_KINDS
                and chunk_bytes > 0 and chunk_bytes % dt.itemsize == 0):
            return "int8"
        return "xor_rle"
    return "xor_rle"  # "xor_rle" and "auto"


class FusedLeafEncoding:
    """One fused device pass over a leaf: chunk fingerprints + the codec
    arithmetic for *every* chunk, via the Pallas codec kernels
    (``repro.kernels.codec`` through the ``kernels/ops.py`` dispatch).

    The registry uses this in place of the fingerprint-then-host-encode
    two-pass flow when the leaf qualifies (see ``Registry._fused_leaf``):
    dirty detection and encoding share a single read of the state, which
    is the device-side analogue of the paper's cheap pre-copy rounds.
    ``fps`` is bit-identical to ``leaf_fingerprints``; ``blob(c)`` is
    byte-identical to the matching host codec's ``encode`` for chunk
    ``c`` — the differential suite (tests/test_codec_kernels.py) pins
    both claims against the host oracle.

    The variable-length RLE pass and blob assembly stay on host: they are
    O(dirty bytes) and data-dependent, the wrong shape for a vector unit.
    An incompressible chunk (raw fallback) is rebuilt on the host from its
    XOR words and the parent's bytes (``raw_seg``), so it costs its own
    bytes and no copy of the leaf; only int8 serializes the leaf, lazily.
    """

    def __init__(self, leaf, parent_buf: bytes, codec_name: str,
                 dtype: np.dtype, chunk_bytes: int):
        from repro.kernels import ops

        assert codec_name in ("xor_rle", "int8"), codec_name
        self.codec_name = codec_name
        self._leaf = leaf
        self._dtype = np.dtype(dtype)
        self._cb = chunk_bytes
        self._nbytes = len(parent_buf)
        self._parent = parent_buf
        self._raw: Optional[bytes] = None
        self._xor = self._q = self._scale = None
        if codec_name == "xor_rle":
            fps, xor = ops.fused_xor_fingerprint(leaf, parent_buf,
                                                 chunk_bytes)
            self._xor = np.asarray(xor)          # [C, R, 128] u32
            down = self._xor.nbytes
        else:
            fps, q, scale = ops.fused_int8_fingerprint(leaf, parent_buf,
                                                       chunk_bytes)
            self._q = np.asarray(q)              # [C, NB, 256] i32
            self._scale = np.asarray(scale)      # [C, NB] f32
            down = self._q.nbytes + self._scale.nbytes
        self.fps = np.asarray(fps)               # [C, 4] u32
        obs.count("push.d2h_bytes", down + self.fps.nbytes)

    def _seg_len(self, c: int) -> int:
        return min(self._cb, self._nbytes - c * self._cb)

    def raw_seg(self, c: int) -> bytes:
        """Raw bytes of chunk ``c``: for ``xor_rle`` its XOR words XOR the
        parent's bytes (exact, and both are on the host already); for
        int8 a slice of the leaf, serialized lazily and memoized."""
        if self._xor is not None:
            n = self._seg_len(c)
            xor = self._xor[c].reshape(-1).view(np.uint8)[:n]
            parent = np.frombuffer(self._parent, np.uint8, count=n,
                                   offset=c * self._cb)
            return (xor ^ parent).tobytes()
        if self._raw is None:
            with obs.span("push.serialize"):
                if not isinstance(self._leaf, np.ndarray):
                    obs.count("push.d2h_bytes", self._leaf.nbytes)
                self._raw = np.asarray(self._leaf).tobytes()
        return self._raw[c * self._cb: c * self._cb + self._cb]

    def blob(self, c: int) -> bytes:
        """The encoded blob for chunk ``c`` — byte-identical to
        ``get_codec(self.codec_name).encode(seg, parent_seg, dtype)``."""
        seg_len = self._seg_len(c)
        if self.codec_name == "xor_rle":
            # kernel word layout zero-pads the tail chunk; the pad XORs to
            # zero (both sides padded), so trimming to seg_len restores
            # exactly the host codec's XOR vector
            x = np.frombuffer(self._xor[c].tobytes()[:seg_len], np.uint8)
            rle = _rle_encode(x)
            if len(rle) + 1 >= seg_len:
                return _RAW_FLAG + self.raw_seg(c)
            return _RLE_FLAG + rle
        from repro.optim.compression import BLOCK

        n_elems = seg_len // self._dtype.itemsize
        nblk = -(-n_elems // BLOCK)
        pad = nblk * BLOCK - n_elems
        q = self._q[c, :nblk].astype(np.int8)
        scale = self._scale[c, :nblk].reshape(-1, 1)
        header = (int(pad).to_bytes(4, "little")
                  + int(q.size).to_bytes(4, "little"))
        return header + q.tobytes() + scale.tobytes()
