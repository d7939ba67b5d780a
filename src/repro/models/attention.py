"""GQA attention block: params, RoPE dispatch, KV-cache management, sharding.

Two activation-sharding strategies (cfg.attn_sharding):
  * "heads": query heads sharded over the ``model`` mesh axis (requires
    num_heads % model_size == 0 — codeqwen/chatglm/granite/qwen2-vl).
  * "seq":   sequence sharded over ``model`` for train/prefill (KV gathered),
    for archs whose head counts don't divide the axis (gemma3/smollm/
    whisper/llama4/recurrentgemma/xlstm).
Decode always shards the KV cache along its sequence axis ("kv_seq" ->
model): single-token attention lowers to flash-decode partial reductions.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import common
from repro.models.common import param, value_of
from repro.sharding.rules import DEFAULT_RULES, with_sharding_constraint_logical


def _act_rules(cfg):
    if cfg.attn_sharding == "seq":
        return DEFAULT_RULES.overriding(
            seq="model", act_heads=None, act_qout=None, act_kv_heads=None
        )
    return DEFAULT_RULES


def constrain(x, axes, cfg):
    return with_sharding_constraint_logical(x, axes, _act_rules(cfg))


def init_attention(key, cfg, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    qdim, kvdim = cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": param(ks[0], (d, qdim), ("embed", "qout")),
        "wk": param(ks[1], (d, kvdim), ("embed", "kv_out")),
        "wv": param(ks[2], (d, kvdim), ("embed", "kv_out")),
        "wo": param(ks[3], (qdim, d), ("qout", "embed")),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = common.zeros_param((hd,), ("stats",))
        p["k_norm"] = common.zeros_param((hd,), ("stats",))
    return p


def _project_qkv(params, x, kv_x, cfg):
    """x [B,S,D] -> q [B,S,H,hd], k/v [B,Skv,Hkv,hd] (pre-RoPE)."""
    B, S, _ = x.shape
    Skv = kv_x.shape[1]
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ value_of(params["wq"]).astype(dt)).reshape(B, S, cfg.num_heads, hd)
    k = (kv_x @ value_of(params["wk"]).astype(dt)).reshape(B, Skv, cfg.num_kv_heads, hd)
    v = (kv_x @ value_of(params["wv"]).astype(dt)).reshape(B, Skv, cfg.num_kv_heads, hd)
    if cfg.use_qk_norm:
        q = common.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, params["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_forward(params, x, positions, cfg, *, local: bool = False,
                 causal: bool = True, kv_x=None, kv_positions=None):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, S, D = x.shape
    kv_x = x if kv_x is None else kv_x
    q, k, v = _project_qkv(params, x, kv_x, cfg)
    if kv_x is x and cfg.rope_kind != "none":
        q = common.rope_for(cfg, q, positions, local)
        k = common.rope_for(
            cfg, k, positions if kv_positions is None else kv_positions, local
        )
    q = constrain(q, ("batch", "seq", "act_heads", None), cfg)
    window = cfg.window if local else 0
    if (local and cfg.attn_sharding == "seq" and kv_x is x
            and S % max(window, 1) == 0):
        # local-window layers never need the full KV: keep K/V seq-sharded;
        # the banded attention's previous-chunk shift lowers to a neighbor
        # collective-permute (halo exchange) instead of a full all-gather
        # (§Perf cell D, EXPERIMENTS.md).
        k = constrain(k, ("batch", "seq", "act_kv_heads", None), cfg)
        v = constrain(v, ("batch", "seq", "act_kv_heads", None), cfg)
    else:
        k = constrain(k, ("batch", None, "act_kv_heads", None), cfg)
        v = constrain(v, ("batch", None, "act_kv_heads", None), cfg)
    out = ops.attention(q, k, v, causal=causal, window=window)
    out = constrain(out, ("batch", "seq", "act_heads", None), cfg)
    out = out.reshape(B, S, -1) @ value_of(params["wo"]).astype(x.dtype)
    return constrain(out, ("batch", "seq", "act_embed"), cfg)


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, seq: int, *, local: bool = False,
                  dtype=None):
    """Cache for one attention layer.  Local layers keep a ring buffer of
    ``window`` slots; global layers keep the full horizon.

    ``cfg.kv_cache_dtype == "int8"`` stores blockwise-quantized K/V (one
    bf16 scale per (slot, kv-head)) — halving decode HBM traffic vs bf16
    (§Perf iteration C2)."""
    S = min(seq, cfg.window) if local else seq
    hd = cfg.resolved_head_dim
    cache = {"pos": jnp.full((batch, S), -1, jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        cache["k"] = jnp.zeros((batch, S, cfg.num_kv_heads, hd), jnp.int8)
        cache["v"] = jnp.zeros((batch, S, cfg.num_kv_heads, hd), jnp.int8)
        cache["k_scale"] = jnp.zeros((batch, S, cfg.num_kv_heads), jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((batch, S, cfg.num_kv_heads), jnp.bfloat16)
    else:
        dt = dtype or jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
        cache["k"] = jnp.zeros((batch, S, cfg.num_kv_heads, hd), dt)
        cache["v"] = jnp.zeros((batch, S, cfg.num_kv_heads, hd), dt)
    return cache


def kv_cache_logical_axes(local: bool = False, quantized: bool = False):
    axes = {
        "k": ("batch", "kv_seq", "act_kv_heads", None),
        "v": ("batch", "kv_seq", "act_kv_heads", None),
        "pos": ("batch", "kv_seq"),
    }
    if quantized:
        axes["k_scale"] = ("batch", "kv_seq", "act_kv_heads")
        axes["v_scale"] = ("batch", "kv_seq", "act_kv_heads")
    return axes


def _quantize_kv(x):
    """x [..., hd] -> (int8 values, bf16 scale[...])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(cache, dt):
    if "k_scale" not in cache:
        return cache["k"], cache["v"]
    k = cache["k"].astype(dt) * cache["k_scale"].astype(dt)[..., None]
    v = cache["v"].astype(dt) * cache["v_scale"].astype(dt)[..., None]
    return k, v


def prefill_into_cache(params, x, positions, cfg, cache, *, local: bool):
    """Run full attention over the prompt AND populate the cache."""
    out = attn_forward(params, x, positions, cfg, local=local)
    _, k, v = _project_qkv(params, x, x, cfg)
    if cfg.rope_kind != "none":
        k = common.rope_for(cfg, k, positions, local)
    # cache slot ids are 1-D: for M-RoPE [3,B,S] the temporal component
    # (index 0) is the causality axis
    pos1d = positions[0] if positions.ndim == 3 else positions
    S_cache = cache["k"].shape[1]
    S = x.shape[1]
    quant = "k_scale" in cache
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        entries = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        entries = {"k": k.astype(cache["k"].dtype),
                   "v": v.astype(cache["v"].dtype)}
    entries["pos"] = pos1d.astype(jnp.int32)
    if S >= S_cache:  # keep last S_cache positions (ring for local layers)
        sl = slice(S - S_cache, S)
        # ring convention: position p lives at slot p % S_cache (decode
        # writes there) -> roll the kept window into ring order
        shift = (S - S_cache) % S_cache
        new = {name: jnp.roll(a[:, sl], shift, axis=1).astype(cache[name].dtype)
               for name, a in entries.items()}
    else:
        new = {name: jax.lax.dynamic_update_slice_in_dim(
                   cache[name], a.astype(cache[name].dtype), 0, axis=1)
               for name, a in entries.items()}
    return out, new


def attn_append(params, x, positions, cfg, cache, *, local: bool):
    """Append a chunk of k tokens to the cache and attend over it.

    x [B,k,D]; positions [B,k] absolute.  The batched-replay path: one call
    folds k messages with parallel (MXU/BLAS-efficient) attention instead of
    k sequential decode steps.
    """
    from repro.kernels import ref as _ref

    B, K, _ = x.shape
    q, k, v = _project_qkv(params, x, x, cfg)
    if cfg.rope_kind != "none":
        q = common.rope_for(cfg, q, positions, local)
        k = common.rope_for(cfg, k, positions, local)
    S_cache = cache["k"].shape[1]
    slots = (positions % S_cache).astype(jnp.int32)  # [B,k]
    b_idx = jnp.arange(B)[:, None]
    new_cache = dict(cache)
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache["k"] = cache["k"].at[b_idx, slots].set(kq)
        new_cache["v"] = cache["v"].at[b_idx, slots].set(vq)
        new_cache["k_scale"] = cache["k_scale"].at[b_idx, slots].set(ks)
        new_cache["v_scale"] = cache["v_scale"].at[b_idx, slots].set(vs)
    else:
        new_cache["k"] = cache["k"].at[b_idx, slots].set(
            k.astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[b_idx, slots].set(
            v.astype(cache["v"].dtype))
    new_cache["pos"] = cache["pos"].at[b_idx, slots].set(
        positions.astype(jnp.int32))
    k_pos = new_cache["pos"]
    k_all, v_all = _dequantize_kv(new_cache, x.dtype)
    out = _ref.chunk_attention(
        q, k_all, v_all, q_pos=positions, k_pos=k_pos,
        window=cfg.window if local else 0)
    out = out.reshape(B, K, -1) @ value_of(params["wo"]).astype(x.dtype)
    return out, new_cache


# lanes whose rows are written by inlined updates; a larger batch writes in
# a loop of that many lanes per trip, so the program does not grow with it
WRITE_UNROLL = 16


def _write_rows(stacks, rows, layer, slot):
    """Row ``b`` of each ``rows[name]`` [B, ...] into ``stacks[name]``
    [L, B, S, ...] at (``layer``, b, ``slot[b]``): one dynamic_update_slice
    per lane and leaf, so stacks carried through the layer loop are updated
    in place."""
    B = slot.shape[0]

    def write(b, stacks):
        return {name: jax.lax.dynamic_update_slice(
                    stack,
                    jax.lax.dynamic_index_in_dim(rows[name], b)[None, None]
                    .astype(stack.dtype),
                    (layer, b, slot[b]) + (0,) * (stack.ndim - 3))
                for name, stack in stacks.items()}

    return jax.lax.fori_loop(0, B, write, stacks,
                             unroll=min(B, WRITE_UNROLL))


def attn_decode(params, x, positions, cfg, cache, layer, *, local: bool):
    """One-token decode into layer ``layer`` of the stacked cache.

    x [B,1,D]; positions [B,1] absolute positions; cache leaves
    [L,B,S,...].  Each lane's key, value (and scales) and position are
    written in place first; attention then reads the layer."""
    B = x.shape[0]
    q, k, v = _project_qkv(params, x, x, cfg)
    if cfg.decode_heads_replicated:
        # flash-decode layout: q replicated over `model`, cache seq-sharded;
        # attention reduces over the sharded seq axis (partials + psum)
        q = with_sharding_constraint_logical(
            q, ("batch", None, None, None), DEFAULT_RULES)
        k = with_sharding_constraint_logical(
            k, ("batch", None, None, None), DEFAULT_RULES)
        v = with_sharding_constraint_logical(
            v, ("batch", None, None, None), DEFAULT_RULES)
    if cfg.rope_kind != "none":
        q = common.rope_for(cfg, q, positions, local)
        k = common.rope_for(cfg, k, positions, local)
    S_cache = cache["k"].shape[2]
    pos_scalar = positions[:, -1] if positions.ndim == 2 else positions[0, :, -1]
    slot = (pos_scalar % S_cache).astype(jnp.int32)  # ring for local layers
    rows = {"pos": pos_scalar.astype(jnp.int32)}
    quant = "k_scale" in cache
    if quant:
        rows["k"], rows["k_scale"] = _quantize_kv(k[:, 0])
        rows["v"], rows["v_scale"] = _quantize_kv(v[:, 0])
    else:
        rows["k"], rows["v"] = k[:, 0], v[:, 0]
    new_cache = _write_rows({name: cache[name] for name in rows}, rows,
                            layer, slot)
    k_pos = common.at_layer(new_cache["pos"], layer)
    if local:
        k_pos = jnp.where(pos_scalar[:, None] - k_pos < cfg.window, k_pos, -1)
    if quant:
        k_all, v_all = _dequantize_kv(common.at_layer(new_cache, layer),
                                      x.dtype)
        out = ops.decode_attention(q, k_all, v_all, pos_scalar, k_pos)
    else:
        out = ops.decode_attention(q, new_cache["k"], new_cache["v"],
                                   pos_scalar, k_pos, layer)
    out = out.reshape(B, 1, -1) @ value_of(params["wo"]).astype(x.dtype)
    return out, new_cache
