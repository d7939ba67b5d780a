"""Differential/property harness for the fused fingerprint+encode codec
kernels (kernels/codec.py) against the pinned host oracle
(checkpoint/codecs.py, tests/test_compression_codecs.py).

Layers, innermost out:

  * kernel level — the Pallas kernels in interpret mode vs their
    blockwise jnp lowerings, bit-for-bit, and both vs the plain
    fingerprint kernel (fusion must not change the fingerprints);
  * codec level — ``FusedLeafEncoding.blob(c)`` vs the host codec's
    ``encode`` per chunk, byte-identical, across dtypes/shapes/
    chunk-boundary straddles and dirt patterns;
  * registry level — whole pushed *images* (ids are manifest hashes, so
    id equality pins chunks, fps, accounting and manifests at once)
    under ``REPRO_CODEC_BACKEND=host`` vs ``kernel``;
  * migration level — end-to-end migrated-state verification with both
    backends under multiple seeds.

Run with ``REPRO_FORCE_PALLAS_INTERPRET=1`` to route the fused ops
through the Pallas kernels (CI does); the default CPU run exercises the
jnp lowerings, which the kernel-level tests here pin to the kernels.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.checkpoint import Registry
from repro.checkpoint import codecs as codecs_mod
from repro.checkpoint.codecs import FusedLeafEncoding, get_codec
from repro.kernels import codec as ck
from repro.kernels import fingerprint as fp
from repro.kernels import ops

try:
    from hypothesis import given, settings
    import conftest as _strat
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CB = 2048  # small 512-aligned chunk grid keeps interpret mode fast

# element counts straddling the word grid (512 B), the quant-block grid
# (256 floats = 2 word rows) and the chunk grid
SIZES = [CB // 4,            # exactly one chunk
         3 * CB // 4 + 7,    # sub-chunk, odd tail
         100,                # sub-row leaf
         129,                # one quant block + 1
         5 * CB // 4,        # two chunks, short second
         2 * (CB // 4) + 1]  # two chunks + one element


def _pair(n, seed=0, kind="stripes", dtype=np.float32):
    rng = np.random.default_rng(seed)
    cur = rng.standard_normal(n).astype(dtype)
    if kind == "clean":
        parent = cur.copy()
    elif kind == "dense":
        parent = rng.standard_normal(n).astype(dtype)
    else:
        parent = cur.copy()
        idx = rng.integers(0, n, size=max(1, n // 50))
        parent[idx] += rng.standard_normal(idx.size).astype(dtype)
    return cur, parent


def _chunks(buf, cb=CB):
    return [buf[i: i + cb] for i in range(0, len(buf), cb)]


# ---------------------------------------------------------------------------
# kernel level: interpret mode vs jnp lowering, bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [CB // 4, 5 * CB // 4, 129])
def test_xor_kernel_interpret_matches_ref(n):
    cur, parent = _pair(n, seed=1)
    words, pwords = ops._codec_words(jnp.asarray(cur), parent.tobytes(),
                                     CB, pair=False)
    lanes_r, xor_r = ck.xor_fp_ref(words, pwords)
    lanes_i, xor_i = ck.xor_fp_lanes(words, pwords, interpret=True)
    np.testing.assert_array_equal(np.asarray(lanes_r), np.asarray(lanes_i))
    np.testing.assert_array_equal(np.asarray(xor_r), np.asarray(xor_i))


@pytest.mark.parametrize("n", [CB // 4, 5 * CB // 4, 129])
def test_int8_kernel_interpret_matches_ref(n):
    cur, parent = _pair(n, seed=2)
    words, pwords = ops._codec_words(jnp.asarray(cur), parent.tobytes(),
                                     CB, pair=True)
    lanes_r, q_r, s_r = ck.int8_fp_ref(words, pwords)
    lanes_i, q_i, s_i = ck.int8_fp_lanes(words, pwords, interpret=True)
    np.testing.assert_array_equal(np.asarray(lanes_r), np.asarray(lanes_i))
    np.testing.assert_array_equal(np.asarray(q_r), np.asarray(q_i))
    np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_i))


def test_fused_fingerprints_match_plain_fingerprint_kernel():
    """Fusing encode into the fingerprint pass must not change the
    fingerprints — including under the int8 path's zero-row padding."""
    cur, parent = _pair(5 * CB // 4, seed=3)
    plain = np.asarray(ops.chunk_fingerprint(cur, CB))
    fps_x, _ = ops.fused_xor_fingerprint(cur, parent.tobytes(), CB)
    fps_q, _, _ = ops.fused_int8_fingerprint(cur, parent.tobytes(), CB)
    np.testing.assert_array_equal(plain, np.asarray(fps_x))
    np.testing.assert_array_equal(plain, np.asarray(fps_q))


def test_force_interpret_env_routes_fused_ops(monkeypatch):
    cur, parent = _pair(3 * CB // 4 + 7, seed=4)
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    fx_p = ops.fused_xor_fingerprint(cur, parent.tobytes(), CB)
    fq_p = ops.fused_int8_fingerprint(cur, parent.tobytes(), CB)
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "0")
    fx_j = ops.fused_xor_fingerprint(cur, parent.tobytes(), CB)
    fq_j = ops.fused_int8_fingerprint(cur, parent.tobytes(), CB)
    for a, b in zip(fx_p + fq_p, fx_j + fq_j):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pair_rows_pads_to_quant_grid():
    words = jnp.zeros((2, 3, fp.LANES), jnp.uint32)
    assert ck.pair_rows(words).shape == (2, 4, fp.LANES)
    even = jnp.zeros((2, 4, fp.LANES), jnp.uint32)
    assert ck.pair_rows(even) is even


# ---------------------------------------------------------------------------
# codec level: kernel-encoded blobs vs the host oracle, byte-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", ["clean", "stripes", "dense"])
def test_fused_xor_blob_byte_identical_to_host(n, kind):
    cur, parent = _pair(n, seed=n, kind=kind)
    praw = parent.tobytes()
    fenc = FusedLeafEncoding(jnp.asarray(cur), praw, "xor_rle",
                             np.dtype(np.float32), CB)
    codec = get_codec("xor_rle")
    for c, (seg, pseg) in enumerate(zip(_chunks(cur.tobytes()),
                                        _chunks(praw))):
        blob = fenc.blob(c)
        assert blob == codec.encode(seg, pseg, np.dtype(np.float32))
        assert codec.decode(blob, pseg, np.dtype(np.float32)) == seg


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", ["clean", "stripes", "dense"])
def test_fused_int8_blob_byte_identical_to_host(n, kind):
    cur, parent = _pair(n, seed=n + 1, kind=kind)
    praw = parent.tobytes()
    fenc = FusedLeafEncoding(jnp.asarray(cur), praw, "int8",
                             np.dtype(np.float32), CB)
    codec = get_codec("int8")
    for c, (seg, pseg) in enumerate(zip(_chunks(cur.tobytes()),
                                        _chunks(praw))):
        blob = fenc.blob(c)
        assert blob == codec.encode(seg, pseg, np.dtype(np.float32))
        # round trip through the host decoder: same lossy reconstruction
        assert codec.decode(blob, pseg, np.dtype(np.float32)) \
            == codec.decode(codec.encode(seg, pseg, np.dtype(np.float32)),
                            pseg, np.dtype(np.float32))


def test_fused_xor_works_for_sub_word_dtypes():
    """xor_rle operates on raw bytes: int8/uint16 leaves must fuse too."""
    for dtype in (np.uint8, np.int16, np.int64):
        rng = np.random.default_rng(7)
        cur = rng.integers(0, 100, 3 * CB // np.dtype(dtype).itemsize
                           ).astype(dtype)
        parent = cur.copy()
        parent[10:20] += 1
        praw = parent.tobytes()
        # numpy leaves stay numpy (jnp would downcast int64 without x64)
        fenc = FusedLeafEncoding(cur, praw, "xor_rle",
                                 np.dtype(dtype), CB)
        codec = get_codec("xor_rle")
        for c, (seg, pseg) in enumerate(zip(_chunks(cur.tobytes()),
                                            _chunks(praw))):
            assert fenc.blob(c) == codec.encode(seg, pseg, np.dtype(dtype))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(pair=_strat.codec_leaf_pairs(max_elems=2048))
    def test_fused_blobs_match_host_property(pair):
        cur, parent = pair
        praw = parent.tobytes()
        for name in ("xor_rle", "int8"):
            fenc = FusedLeafEncoding(jnp.asarray(cur), praw, name,
                                     np.dtype(np.float32), CB)
            codec = get_codec(name)
            for c, (seg, pseg) in enumerate(zip(_chunks(cur.tobytes()),
                                                _chunks(praw))):
                assert fenc.blob(c) == codec.encode(seg, pseg,
                                                    np.dtype(np.float32))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_fused_blobs_match_host_property():
        pass


# ---------------------------------------------------------------------------
# registry level: whole images identical across backends
# ---------------------------------------------------------------------------

def _push_chain(root, backend, monkeypatch, cb=CB):
    monkeypatch.setenv("REPRO_CODEC_BACKEND", backend)
    rng = np.random.default_rng(11)
    reg = Registry(root, chunk_bytes=cb)
    w = rng.standard_normal(3000).astype(np.float32)
    kv = rng.standard_normal(1200).astype(np.float32)
    ints = rng.integers(0, 255, 5000).astype(np.uint8)
    odd = np.array([1.5, 2.5, 3.5])  # float64: int8 falls back to host
    ids, reports = [], []
    parent = reg.push_image(
        {"state": {"w": w, "kv": kv, "ints": ints, "odd": odd}}).image_id
    ids.append(parent)
    for step in range(3):
        kv = kv.copy()
        kv[rng.integers(0, kv.size, 40)] += \
            rng.standard_normal(40).astype(np.float32)
        ints = ints.copy()
        ints[:17] += 1
        tree = {"w": w, "kv": kv, "ints": ints, "odd": odd}
        for comp, exact in [("xor_rle", True), ("int8", False),
                            ("auto", False)]:
            rep = reg.push_delta({"state": tree}, parent,
                                 compression=comp, exact=exact)
            ids.append(rep.image_id)
            reports.append((rep.wire_bytes, rep.delta_bytes,
                            rep.enc_raw_bytes, rep.fp_bytes,
                            rep.fp_clean_chunks, rep.lossy,
                            rep.written_bytes, rep.deduped_bytes))
            parent = rep.image_id
    flush = reg.push_delta({"state": tree}, parent, compression="int8",
                           exact=True)
    ids.append(flush.image_id)
    pulled, _ = reg.pull_image(flush.image_id)
    got = pulled["state"]
    for k, v in tree.items():
        np.testing.assert_array_equal(got[k], v)
    return ids, reports


def test_registry_images_identical_across_backends(tmp_path, monkeypatch):
    """Image ids are manifest hashes: equality pins every chunk key,
    every fingerprint and every accounting field across the host and
    kernel encode paths at once."""
    ids_h, rep_h = _push_chain(str(tmp_path / "host"), "host", monkeypatch)
    ids_k, rep_k = _push_chain(str(tmp_path / "kernel"), "kernel",
                               monkeypatch)
    assert ids_h == ids_k
    assert rep_h == rep_k


def test_fused_path_engages_only_where_valid(tmp_path, monkeypatch):
    reg = Registry(str(tmp_path), chunk_bytes=CB)
    f32 = np.arange(CB, dtype=np.float32)
    f64 = np.arange(CB, dtype=np.float64)
    full = reg.push_image({"state": {"a": f32, "b": f64}})
    memo = {}
    args = dict(parent=full.image_id, name="state", n=f32.nbytes // CB,
                memo=memo)
    assert reg._fused_leaf(f32, "xor_rle", "float32", f32.nbytes,
                           i=0, **args) is not None
    assert reg._fused_leaf(f32, "int8", "float32", f32.nbytes,
                           i=0, **args) is not None
    # int8 kernel is f32-only; xor still fuses for f64
    args64 = dict(parent=full.image_id, name="state",
                  n=f64.nbytes // CB, memo=memo)
    assert reg._fused_leaf(f64, "int8", "float64", f64.nbytes,
                           i=1, **args64) is None
    assert reg._fused_leaf(f64, "xor_rle", "float64", f64.nbytes,
                           i=1, **args64) is not None
    # "none" never fuses; host backend disables fusion wholesale
    assert reg._fused_leaf(f32, "none", "float32", f32.nbytes,
                           i=0, **args) is None
    monkeypatch.setenv("REPRO_CODEC_BACKEND", "host")
    assert reg._fused_leaf(f32, "xor_rle", "float32", f32.nbytes,
                           i=0, **args) is None


def test_unaligned_chunk_grid_disables_fusion_not_correctness(tmp_path,
                                                              monkeypatch):
    """A chunk grid off the 512-byte word layout can't fuse — pushes
    must silently take the host path, not crash."""
    monkeypatch.setenv("REPRO_CODEC_BACKEND", "kernel")
    reg = Registry(str(tmp_path), chunk_bytes=1000)
    base = {"a": np.arange(2000, dtype=np.float32)}
    full = reg.push_image({"state": base})
    mut = {"a": base["a"] + 1.0}
    delta = reg.push_delta({"state": mut}, full.image_id,
                           compression="xor_rle")
    pulled, _ = reg.pull_image(delta.image_id)
    np.testing.assert_array_equal(pulled["state"]["a"], mut["a"])


def test_codec_backend_env_validated(monkeypatch):
    monkeypatch.setenv("REPRO_CODEC_BACKEND", "gpu")
    with pytest.raises(ValueError, match="REPRO_CODEC_BACKEND"):
        codecs_mod.codec_backend()


# ---------------------------------------------------------------------------
# migration level: end-to-end verification under multiple seeds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [2, 7])
def test_migration_identical_across_backends(tmp_path, seed, monkeypatch):
    from repro.core import MigrationPolicy, run_migration_experiment
    from test_compression_codecs import StripedBlobConsumer

    rows = {}
    for backend in ("host", "kernel"):
        monkeypatch.setenv("REPRO_CODEC_BACKEND", backend)
        r = run_migration_experiment(
            "ms2m_precopy", 10.0,
            registry_root=str(tmp_path / backend), seed=seed,
            worker_factory=StripedBlobConsumer, chunk_bytes=64 * 1024,
            policy=MigrationPolicy(compression="auto",
                                   precopy_max_rounds=3))
        assert r.verified and r.report.state_verified
        rows[backend] = r.row()
    assert rows["host"] == rows["kernel"]


# ---------------------------------------------------------------------------
# roofline calibration plumbing
# ---------------------------------------------------------------------------

def test_timing_constants_from_roofline_is_opt_in():
    """Measured throughput only enters via the constructor; the class
    defaults (which every regression timeline is pinned to) stay the
    paper-fitted constants."""
    from repro.cluster.cluster import TimingConstants

    d = TimingConstants()
    assert d.codec_Bps == 1.2e9 and d.fingerprint_Bps == 24e9
    cal = {"calibration": {"codec_Bps": 5e8, "fingerprint_Bps": 1e9}}
    tc = TimingConstants.from_roofline(cal)
    assert tc.codec_Bps == 5e8 and tc.fingerprint_Bps == 1e9
    assert tc.checkpoint_s == d.checkpoint_s
    assert TimingConstants.from_roofline(cal, codec_Bps=7e8).codec_Bps == 7e8
    # a bare calibration dict (no wrapper) is accepted too
    assert TimingConstants.from_roofline(
        {"codec_Bps": 2e8, "fingerprint_Bps": 0}).fingerprint_Bps == 24e9

