"""Serving driver: batched decode with KV-cache management — the worker
type that MS2M migrates.  ``--smoke`` takes the reduced config, which runs
on a CPU; without it the published config runs, which wants a TPU.
Exits 1 if any prefill or decode logit is not finite.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
      --requests 16 --decode-steps 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.train import step as steplib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8, help="batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B = args.requests

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.asarray(
            rng.normal(0, 0.02, (B, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    if cfg.frontend == "image_patches":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(0, 0.02, (B, cfg.num_patches, cfg.d_model)), jnp.float32)

    prefill = jax.jit(steplib.build_prefill_step(cfg), donate_argnums=(1,))
    decode = jax.jit(steplib.build_decode_step(cfg), donate_argnums=(1,))

    cache = T.init_cache(cfg, B, args.max_seq)
    t0 = time.perf_counter()
    logits, cache = prefill(params, cache, batch)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    finite = jnp.isfinite(logits).all()
    print(f"[serve] prefill {args.prompt_len} tokens x {B} requests: "
          f"{t_prefill*1e3:.0f}ms")

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    pos = jnp.full((B, 1), args.prompt_len, jnp.int32)
    generated = [tok]
    t0 = time.perf_counter()
    for i in range(args.decode_steps):
        logits, cache = decode(params, cache, tok, pos)
        finite &= jnp.isfinite(logits).all()
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = pos + 1
        generated.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    toks_s = B * args.decode_steps / dt
    print(f"[serve] decoded {args.decode_steps} steps x {B} requests: "
          f"{dt*1e3:.0f}ms ({toks_s:.0f} tok/s)")
    out = jnp.concatenate(generated, axis=1)
    print(f"[serve] sample continuation (request 0): {np.asarray(out[0])[:16]}")
    if not bool(finite):
        print("[serve] FAIL: non-finite logits")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
