"""Bring-up check of the main path on one TPU.

    python chip_smoke.py

Runs in this one process, phase by phase, and stops at the first failure
with a nonzero exit:

  1. device   -- JAX must see a TPU; anything else exits nonzero, naming
                 what it found (there is no CPU fallback);
  2. kernels  -- the fingerprint / xor / int8 codec kernels on seeded
                 device arrays at the registry's two chunk grids, bit for
                 bit against their jnp references on the chip and against
                 numpy; decode and flash attention at smollm_360m widths
                 against ``kernels/ref.py`` within a bf16 tolerance;
  3. serve    -- ``repro.launch.serve.main`` at the full smollm_360m
                 config (8 requests, 32-token prompt, 32 decode steps);
  4. migrate  -- ``repro.launch.migrate.main`` twice without
                 ``--hash-consumer``: a serving replica (``ServingEngine``,
                 ``serving_handoff``, ``xor_rle``) and the fold consumer
                 (``ms2m_precopy``, ``int8``).  Each must verify, and every
                 leaf its pushes delta-encoded must have taken the fused
                 device kernels.

Each phase prints its exit code and wall time (compilation included).  The
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import re
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# the registry's chunk grids, as (leaf shape, chunk bytes): the serving
# engine's slot-aligned 512-byte grid over a paper_consumer KV leaf
# (8 slots x 128 positions x 4 kv heads x 32), and one whole 1 MiB leaf on
# the default 4 MiB grid (the fold consumer's KV leaf, max_seq=2048)
CODEC_GRIDS = (((8, 128, 4, 32), 512), ((1, 2048, 4, 32), 4 * 1024 * 1024))
# attention at smollm_360m widths: 15 query heads, 5 kv heads, head_dim 64
SMOLLM_HEADS = dict(H=15, Hkv=5, D=64)
# bf16 outputs against an f32 reference: |got - want| <= ATOL + RTOL*|want|
ATTN_TOL = dict(atol=2e-2, rtol=2e-2)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu():
    """-> (platform, device_kind, count); raises unless JAX sees a TPU."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX found {len(devices)} {d.platform} device(s) "
            f"({d.device_kind}); this check runs on a TPU only")
    return d.platform, d.device_kind, len(devices)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _numpy_quant(delta):
    """The int8 quantizer in numpy (IEEE f32): ``[NB, 256]`` -> q, scale."""
    import numpy as np

    from repro.optim.compression import _INV127

    scale = np.abs(delta).max(axis=1, keepdims=True) * _INV127
    scale = np.maximum(scale, np.float32(1e-12))
    q = np.clip(np.rint(delta / scale), -127, 127).astype(np.int32)
    return q, scale[:, 0]


def _codec_kernels(shape, chunk_bytes: int, rng) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.codecs import FusedLeafEncoding, get_codec
    from repro.kernels import codec as ck
    from repro.kernels import fingerprint as fp

    host_cur = rng.standard_normal(shape).astype(np.float32)
    parent = host_cur.copy()
    flat = parent.reshape(-1)
    for start in rng.integers(0, flat.size - 64, size=max(4, flat.size // 4096)):
        flat[start: start + 48] += rng.standard_normal(48).astype(np.float32)
    cur = jax.device_put(host_cur)
    words = fp.chunked_words(cur, chunk_bytes)
    pwords = fp.chunked_words(parent, chunk_bytes)
    C, R, _ = words.shape
    tag = f"grid C={C} R={R} ({chunk_bytes} B chunks)"

    def same(a, b, what):
        a, b = np.asarray(a), np.asarray(b)
        check(a.shape == b.shape and np.array_equal(a, b),
              f"{tag}: {what} differs in {int(np.sum(a != b))} of {a.size}")

    lanes = fp.fingerprint_lanes(words)
    same(lanes, jax.jit(fp.fingerprint_lanes_ref)(words),
         "fingerprint_lanes vs fingerprint_lanes_ref")
    same(lanes, fp.fingerprint_lanes_ref(np.asarray(words)),
         "fingerprint_lanes vs the reference on numpy words")

    lanes_x, xor = ck.xor_fp_lanes(words, pwords)
    ref_lanes, ref_xor = jax.jit(ck.xor_fp_ref)(words, pwords)
    same(lanes_x, ref_lanes, "xor_fp_lanes fingerprint vs xor_fp_ref")
    same(xor, ref_xor, "xor_fp_lanes xor vs xor_fp_ref")
    same(xor, np.asarray(words) ^ np.asarray(pwords), "xor vs numpy xor")

    pw, ppw = ck.pair_rows(words), ck.pair_rows(pwords)
    lanes_q, q, scale = ck.int8_fp_lanes(pw, ppw)
    ref_lanes, ref_q, ref_scale = jax.jit(ck.int8_fp_ref)(pw, ppw)
    same(lanes_q, lanes, "int8_fp_lanes fingerprint vs fingerprint_lanes")
    same(lanes_q, ref_lanes, "int8_fp_lanes fingerprint vs int8_fp_ref")
    same(q, ref_q, "int8_fp_lanes q vs int8_fp_ref")
    same(scale, ref_scale, "int8_fp_lanes scale vs int8_fp_ref")
    delta = (np.asarray(pw).view(np.float32)
             - np.asarray(ppw).view(np.float32)).reshape(-1, ck.QBLOCK)
    np_q, np_scale = _numpy_quant(delta)
    same(scale, np_scale.reshape(np.asarray(scale).shape),
         "int8 scale vs numpy")
    same(q, np_q.reshape(np.asarray(q).shape), "int8 q vs numpy")

    # the registry's blobs: fused device pass vs the host codecs, per chunk
    raw, praw = host_cur.tobytes(), parent.tobytes()
    for name in ("xor_rle", "int8"):
        fenc = FusedLeafEncoding(cur, praw, name, np.dtype(np.float32),
                                 chunk_bytes)
        codec = get_codec(name)
        bad = sum(
            fenc.blob(c) != codec.encode(raw[o: o + chunk_bytes],
                                         praw[o: o + chunk_bytes],
                                         np.dtype(np.float32))
            for c, o in enumerate(range(0, len(raw), chunk_bytes)))
        check(bad == 0, f"{tag}: {bad} {name} blob(s) differ from the "
                        f"host codec")
    log(f"kernels {tag}: fingerprint, xor and int8 match their references "
        f"and numpy bit for bit")


def _attention_kernels(rng) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import decode_attention as da
    from repro.kernels import flash_attention as fa
    from repro.kernels import ref
    from repro.kernels.ops import _fit_block

    H, Hkv, D = SMOLLM_HEADS["H"], SMOLLM_HEADS["Hkv"], SMOLLM_HEADS["D"]
    bf16 = jnp.bfloat16

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), bf16)

    def close(got, want, what):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = np.abs(got - want)
        bound = ATTN_TOL["atol"] + ATTN_TOL["rtol"] * np.abs(want)
        check(got.shape == want.shape and np.isfinite(got).all()
              and (err <= bound).all(),
              f"{what}: max |err| {float(err.max()):.3g} exceeds "
              f"{ATTN_TOL['atol']} + {ATTN_TOL['rtol']}*|ref|")
        return float(err.max())

    f32 = lambda x: x.astype(jnp.float32)
    # decode: the serving engine's shape, 8 slots over a 128-position cache
    B, S = 8, 128
    q, kc, vc = normal(B, 1, H, D), normal(B, S, Hkv, D), normal(B, S, Hkv, D)
    q_pos = jnp.asarray(rng.integers(0, S, B), jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    k_pos = jnp.where(k_pos <= q_pos[:, None], k_pos, -1)
    got = da.decode_attention(q, kc, vc, q_pos, k_pos)
    with jax.default_matmul_precision("float32"):
        want = ref.decode_attention(f32(q), f32(kc), f32(vc), q_pos=q_pos,
                                    k_pos=k_pos)
    err_d = close(got, want, f"decode_attention B={B} S={S} H={H} Hkv={Hkv} "
                             f"D={D} bf16")
    # prefill: 8 prompts of 32 tokens, causal
    B, S = 8, 32
    q, k, v = normal(B, S, H, D), normal(B, S, Hkv, D), normal(B, S, Hkv, D)
    got = fa.flash_attention(q, k, v, causal=True,
                             block_q=_fit_block(S, 512),
                             block_k=_fit_block(S, 512))
    with jax.default_matmul_precision("float32"):
        want = ref.naive_attention(f32(q), f32(k), f32(v), causal=True)
    err_f = close(got, want, f"flash_attention B={B} S={S} H={H} Hkv={Hkv} "
                             f"D={D} bf16")
    log(f"kernels attention at smollm_360m widths: decode max|err| "
        f"{err_d:.3g}, flash max|err| {err_f:.3g} (bound {ATTN_TOL['atol']}"
        f" + {ATTN_TOL['rtol']}*|ref| against an f32 reference)")


def phase_kernels() -> int:
    import numpy as np

    rng = np.random.default_rng(SEED)
    for shape, chunk_bytes in CODEC_GRIDS:
        _codec_kernels(shape, chunk_bytes, rng)
    _attention_kernels(rng)
    return 0


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def phase_serve() -> int:
    from repro.launch import serve

    return serve.main(["--arch", "smollm_360m", "--requests", "8",
                       "--prompt-len", "32", "--decode-steps", "32",
                       "--max-seq", "128"])


_LEAVES_RE = re.compile(r"fused_leaves=(\d+) host_codec_leaves=(\d+)")


def _migrate(argv) -> int:
    """``migrate.main`` on a private registry; its output is echoed, and
    its summary line must show that every delta-encoded leaf took the
    fused device kernels."""
    from repro.launch import migrate

    with tempfile.TemporaryDirectory(prefix="smoke-registry-") as reg:
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = migrate.main(list(argv) + ["--registry", reg])
    text = out.getvalue()
    print(text, end="", flush=True)
    if rc != 0:
        return rc
    counts = _LEAVES_RE.findall(text)
    check(len(counts) == 1, "migrate printed no fused_leaves count")
    fused, host = map(int, counts[0])
    check(fused > 0 and host == 0,
          f"{host} leaf encoding(s) took the host codecs instead of the "
          f"fused device kernels ({fused} fused)")
    log(f"every delta-encoded leaf took the fused kernels: "
        f"{fused} fused, {host} host")
    return rc


def phase_migrate_serving() -> int:
    return _migrate(["--workload", "serving", "--strategy",
                     "serving_handoff", "--compression", "xor_rle"])


def phase_migrate_fold() -> int:
    return _migrate(["--strategy", "ms2m_precopy", "--compression", "int8"])


PHASES = (("kernels", phase_kernels),
          ("serve smollm_360m", phase_serve),
          ("migrate serving_handoff xor_rle", phase_migrate_serving),
          ("migrate fold ms2m_precopy int8", phase_migrate_fold))


def main() -> int:
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print("[smoke] FAIL: src/repro is not next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        platform, kind, count = require_tpu()
    except RuntimeError as e:   # SmokeFailure, or no backend starts at all
        print(f"[smoke] FAIL device: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device platform={platform} kind={kind} count={count}")
    log(f"compile cache: {enable_compile_cache()}")
    for name, phase in PHASES:
        t0 = time.perf_counter()
        try:
            rc = phase()
        except SmokeFailure as e:
            print(f"[smoke] FAIL {name}: {e}", file=sys.stderr)
            rc = 1
        log(f"phase={name} rc={rc} wall_s={time.perf_counter() - t0:.3f} "
            f"(compile included)")
        if rc != 0:
            print(f"[smoke] FAIL {name}: exit code {rc}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
