"""Unified LM covering all ten architectures.

The decoder is a ``lax.scan`` over *layer groups* — one group is the
architecture's repeating pattern (e.g. gemma3's 5 local + 1 global) — so an
80-layer model compiles one group body once.  Per-group params/caches are
stacked along a leading ``layers`` axis.

Entry points:
  init_lm / lm_forward / lm_loss              — training
  init_cache / lm_prefill / lm_decode_step    — serving
All are pure functions of (params, inputs); caches are explicit pytrees —
which is exactly what makes MS2M replay bit-exact (core/replay.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, common, mlp, moe, rglru, xlstm
from repro.models.config import BlockKind, ModelConfig
from repro.models.common import ParamLeaf, param, value_of, zeros_param
from repro.sharding.rules import with_sharding_constraint_logical as constrain

MIXERS_WITH_KV = (BlockKind.ATTN_GLOBAL, BlockKind.ATTN_LOCAL)


def _scan_or_unroll(body, x, xs, unroll: bool):
    """lax.scan, or an inlined python loop for cost-calibration lowers."""
    if not unroll:
        return jax.lax.scan(body, x, xs)
    G = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for gi in range(G):
        x, y = body(x, jax.tree.map(lambda a: a[gi], xs))
        ys.append(y)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return x, stacked


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg, mixer: BlockKind, ffn: BlockKind, cross: bool):
    ks = jax.random.split(key, 5)
    blk: Dict[str, Any] = {"norm1": zeros_param((cfg.d_model,), ("embed",))}
    if mixer in MIXERS_WITH_KV:
        blk["attn"] = attention.init_attention(ks[0], cfg)
    elif mixer == BlockKind.RGLRU:
        blk["rglru"] = rglru.init_rglru_block(ks[0], cfg)
    elif mixer == BlockKind.MLSTM:
        blk["mlstm"] = xlstm.init_mlstm_block(ks[0], cfg)
    elif mixer == BlockKind.SLSTM:
        blk["slstm"] = xlstm.init_slstm_block(ks[0], cfg)
    if cross:
        blk["cross_attn"] = attention.init_attention(ks[3], cfg, cross=True)
        blk["norm_cross"] = zeros_param((cfg.d_model,), ("embed",))
    if ffn == BlockKind.MLP:
        blk["norm2"] = zeros_param((cfg.d_model,), ("embed",))
        blk["mlp"] = mlp.init_mlp(ks[1], cfg)
    elif ffn == BlockKind.MOE:
        blk["norm2"] = zeros_param((cfg.d_model,), ("embed",))
        blk["moe"] = moe.init_moe(ks[2], cfg)
    return blk


def _stack_layers(tree):
    """Prefix every ParamLeaf's logical axes with 'layers' (post-vmap)."""
    return jax.tree.map(
        lambda p: ParamLeaf(p.value, ("layers",) + p.axes),
        tree, is_leaf=common.is_param,
    )


def _init_groups(key, cfg, n_groups: int, cross: bool = False):
    """Stacked per-position block params: {'b0': stacked, 'b1': ...}."""
    groups = {}
    for i, (mixer, ffn) in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(key, i), n_groups)
        stacked = jax.vmap(
            lambda k: _init_block(k, cfg, mixer, ffn, cross)
        )(keys)
        groups[f"b{i}"] = _stack_layers(stacked)
    return groups


def init_lm(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": common.init_embedding(ks[0], cfg),
        "groups": _init_groups(ks[1], cfg, cfg.num_groups,
                               cross=cfg.is_encoder_decoder),
        "final_norm": zeros_param((cfg.d_model,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = param(
            ks[2], (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), scale=0.02
        )
    if cfg.is_encoder_decoder:
        enc_cfg = cfg  # same dims; whisper enc/dec share d_model
        assert cfg.num_encoder_layers % 1 == 0
        params["encoder"] = {
            "groups": _init_groups(ks[3], enc_cfg, cfg.num_encoder_layers),
            "final_norm": zeros_param((cfg.d_model,), ("embed",)),
        }
        params["dec_pos_embed"] = param(
            ks[4], (8192, cfg.d_model), (None, "embed"), scale=0.02
        )  # learned decoder positions (whisper), capped at 8192 and tiled
    if cfg.frontend == "image_patches":
        params["patch_adapter"] = param(
            ks[5], (cfg.d_model, cfg.d_model), ("embed", "act_embed"), scale=0.02
        )
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _apply_block(blk, x, positions, cfg, i: int, *, enc_out=None,
                 causal: bool = True):
    """Full-sequence (train/prefill-without-cache) block application."""
    mixer, ffn = cfg.pattern[i]
    aux = jnp.zeros((), jnp.float32)
    h = common.rms_norm(x, blk["norm1"], cfg.norm_eps)
    if mixer in MIXERS_WITH_KV:
        local = mixer == BlockKind.ATTN_LOCAL
        y = attention.attn_forward(blk["attn"], h, positions, cfg,
                                   local=local, causal=causal)
    elif mixer == BlockKind.RGLRU:
        y, _ = rglru.rglru_block_forward(blk["rglru"], h, cfg)
    elif mixer == BlockKind.MLSTM:
        y, _ = xlstm.mlstm_block_forward(blk["mlstm"], h, cfg)
    elif mixer == BlockKind.SLSTM:
        y, _ = xlstm.slstm_block_forward(blk["slstm"], h, cfg)
    else:
        raise ValueError(mixer)
    x = x + y
    if enc_out is not None and "cross_attn" in blk:
        h = common.rms_norm(x, blk["norm_cross"], cfg.norm_eps)
        y = attention.attn_forward(blk["cross_attn"], h, positions, cfg,
                                   causal=False, kv_x=enc_out)
        x = x + y
    if ffn == BlockKind.MLP:
        h = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
        x = x + mlp.mlp_forward(blk["mlp"], h, cfg)
    elif ffn == BlockKind.MOE:
        h = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
        y, aux = moe.moe_forward(blk["moe"], h, cfg)
        x = x + y
    return x, aux


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    policy = {
        "full": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[remat]
    return jax.checkpoint(fn, policy=policy)


def _run_groups(groups, x, positions, cfg, *, enc_out=None, causal=True,
                remat: str = "none", n_positions: Optional[int] = None,
                unroll: bool = False):
    """Scan the stacked group params over the activations.

    ``unroll=True`` applies the groups as an inlined python loop instead of
    ``lax.scan`` — used by the dry-run's cost-calibration lowers (XLA cost
    analysis counts a while-loop body once, so per-layer costs are derived
    from small unrolled variants; see launch/dryrun.py).
    """
    npos = n_positions or len(cfg.pattern)

    def body(carry, group_params):
        x, aux = carry

        def inner(x):
            a = jnp.zeros((), jnp.float32)
            for i in range(npos):
                x, ai = _apply_block(group_params[f"b{i}"], x, positions, cfg,
                                     i, enc_out=enc_out, causal=causal)
                a = a + ai
            return x, a

        x, a = _remat_wrap(inner, remat)(x)
        return (x, aux + a), None

    carry = (x, jnp.zeros((), jnp.float32))
    if unroll:
        G = jax.tree.leaves(groups)[0].shape[0]
        for gi in range(G):
            gp = jax.tree.map(lambda a: a[gi], groups)
            carry, _ = body(carry, gp)
        x, aux = carry
    else:
        (x, aux), _ = jax.lax.scan(body, carry, groups)
    return x, aux


# ---------------------------------------------------------------------------
# forward / loss (train)
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg):
    """tokens (+ stub modality embeddings) -> x [B,S,D], positions."""
    x = common.embed(params["embed"], batch["tokens"], cfg)
    positions = batch.get("positions")
    if positions is None:
        B, S = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.frontend == "image_patches" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = pe @ value_of(params["patch_adapter"]).astype(x.dtype)
        P = pe.shape[1]
        x = jax.lax.dynamic_update_slice_in_dim(x, pe, 1, axis=1)
    return constrain(x, ("batch", "seq", "act_embed")), positions


def _encode(params, batch, cfg, unroll: bool = False):
    """Whisper encoder over precomputed (stub) frame embeddings."""
    frames = batch["frames"].astype(cfg.compute_dtype)  # [B, F, D]
    F = frames.shape[1]
    pos = common.sinusoidal_positions(F, cfg.d_model).astype(frames.dtype)
    x = frames + pos[None]
    x = constrain(x, ("batch", "seq", "act_embed"))
    B = frames.shape[0]
    positions = jnp.broadcast_to(jnp.arange(F)[None], (B, F))
    enc = params["encoder"]
    x, _ = _run_groups(enc["groups"], x, positions, cfg, causal=False,
                       unroll=unroll)
    return common.rms_norm(x, enc["final_norm"], cfg.norm_eps)


def _logits(params, x, cfg):
    # a decode step's float32 residual is rounded once, after the norm
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps).astype(
        cfg.compute_dtype)
    table = (params["unembed"] if "unembed" in params
             else params["embed"]["table"])
    logits = jnp.einsum(
        "bsd,vd->bsv", x, value_of(table).astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    logits = common.soft_cap(logits, cfg.logits_softcap)
    return constrain(logits, ("batch", None, "act_vocab"))


def lm_forward(params, batch, cfg: ModelConfig, *, remat: str = "none",
               unroll: bool = False):
    """batch: tokens [B,S] (+frames/patch_embeds/positions). -> (logits, aux)."""
    enc_out = (_encode(params, batch, cfg, unroll=unroll)
               if cfg.is_encoder_decoder else None)
    x, positions = _embed_inputs(params, batch, cfg)
    if cfg.is_encoder_decoder:
        S = x.shape[1]
        pe = value_of(params["dec_pos_embed"]).astype(x.dtype)
        idx = jnp.arange(S) % pe.shape[0]
        x = x + pe[idx][None]
    x, aux = _run_groups(params["groups"], x, positions, cfg,
                         enc_out=enc_out, remat=remat, unroll=unroll)
    return _logits(params, x, cfg), aux


def lm_loss(params, batch, cfg: ModelConfig, *, remat: str = "none",
            unroll: bool = False):
    """Next-token cross-entropy with masking; returns (loss, metrics)."""
    logits, aux = lm_forward(params, batch, cfg, remat=remat, unroll=unroll)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    xent = -(ll * mask).sum() / denom
    loss = xent + cfg.router_aux_coef * aux
    return loss, {"xent": xent, "aux": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def _init_block_cache(cfg, i: int, batch: int, seq: int):
    mixer, _ = cfg.pattern[i]
    if mixer in MIXERS_WITH_KV:
        return attention.init_kv_cache(
            cfg, batch, seq, local=(mixer == BlockKind.ATTN_LOCAL))
    if mixer == BlockKind.RGLRU:
        return rglru.init_rglru_state(cfg, batch)
    if mixer == BlockKind.MLSTM:
        return xlstm.init_mlstm_state(cfg, batch)
    if mixer == BlockKind.SLSTM:
        return xlstm.init_slstm_state(cfg, batch)
    raise ValueError(mixer)


def _block_cache_axes(cfg, i: int):
    mixer, _ = cfg.pattern[i]
    if mixer in MIXERS_WITH_KV:
        return attention.kv_cache_logical_axes(
            quantized=cfg.kv_cache_dtype == "int8")
    if mixer == BlockKind.RGLRU:
        return rglru.rglru_state_logical_axes()
    if mixer == BlockKind.MLSTM:
        return xlstm.mlstm_state_logical_axes()
    if mixer == BlockKind.SLSTM:
        return xlstm.slstm_state_logical_axes()
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, batch: int, seq: int):
    """Decode cache: per-pattern-position trees stacked over groups."""
    G = cfg.num_groups
    cache = {}
    for i in range(len(cfg.pattern)):
        one = _init_block_cache(cfg, i, batch, seq)
        cache[f"b{i}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (G,) + a.shape), one
        )
    if cfg.is_encoder_decoder:
        cache["enc_out"] = jnp.zeros(
            (batch, cfg.encoder_seq, cfg.d_model), cfg.compute_dtype)
    return cache


def cache_logical_axes(cfg: ModelConfig):
    axes = {}
    for i in range(len(cfg.pattern)):
        ax = _block_cache_axes(cfg, i)
        axes[f"b{i}"] = jax.tree.map(
            lambda a: ("layers",) + a,
            ax, is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(e, (str, type(None))) for e in t),
        )
    if cfg.is_encoder_decoder:
        axes["enc_out"] = ("batch", None, "act_embed")
    return axes


def _apply_block_decode(blk, stack, layer, x, positions, cfg, i: int, *,
                        enc_out, experts=None):
    """One block of one decode step against layer ``layer`` of the stacked
    cache ``stack``; returns the new activations and stack, and for a block
    with routed experts the expert weight fetches per lane (else None).
    An attention cache takes one row per lane in place; a recurrent state
    is rewritten whole every step, so its layer is written back whole.
    Routed experts are read from ``experts``, the stack of the block's
    expert weights, at ``layer``, or else from ``blk``'s own.

    The residual stream ``x`` is carried in float32: each sublayer reads
    it normed and rounded once to the compute dtype, and its output joins
    it without a rounding of the sum. Rounded to bfloat16 at every one of
    a deep model's additions, it drifts far enough from the model to flip
    routed experts at near ties (see ``moe.moe_decode``)."""
    mixer, ffn = cfg.pattern[i]
    fetches = None
    cdt = cfg.compute_dtype
    x = x.astype(jnp.float32)
    h = common.rms_norm(x, blk["norm1"], cfg.norm_eps).astype(cdt)
    if mixer in MIXERS_WITH_KV:
        local = mixer == BlockKind.ATTN_LOCAL
        y, stack = attention.attn_decode(blk["attn"], h, positions, cfg,
                                         stack, layer, local=local)
    else:
        state = common.at_layer(stack, layer)
        if mixer == BlockKind.RGLRU:
            y, state = rglru.rglru_decode_step(blk["rglru"], h, cfg, state)
        elif mixer == BlockKind.MLSTM:
            y, state = xlstm.mlstm_decode_step(blk["mlstm"], h, cfg, state)
        elif mixer == BlockKind.SLSTM:
            y, state = xlstm.slstm_decode_step(blk["slstm"], h, cfg, state)
        else:
            raise ValueError(mixer)
        stack = jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, layer, 0),
            stack, state)
    x = x + y
    if enc_out is not None and "cross_attn" in blk:
        h = common.rms_norm(x, blk["norm_cross"], cfg.norm_eps).astype(cdt)
        y = attention.attn_forward(blk["cross_attn"], h, positions, cfg,
                                   causal=False, kv_x=enc_out)
        x = x + y
    if ffn == BlockKind.MLP:
        h = common.rms_norm(x, blk["norm2"], cfg.norm_eps).astype(cdt)
        x = x + mlp.mlp_forward(blk["mlp"], h, cfg)
    elif ffn == BlockKind.MOE:
        # in float32: the router reads the normed input unrounded
        h = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
        y, fetches = moe.moe_decode(blk["moe"], h, cfg,
                                    _lane_positions(positions) >= 0,
                                    experts, layer)
        x = x + y
    return x, stack, fetches


def _lane_positions(positions):
    """Each lane's position in a decode step ([B,1], or mrope's [3,B,1])."""
    return positions[:, -1] if positions.ndim == 2 else positions[0, :, -1]


def lm_decode_step(params, tokens, positions, cfg: ModelConfig, cache,
                   unroll: bool = False):
    """One decode step.  tokens [B,1]; positions [B,1] -> (logits, cache).

    The stacked cache is the layer loop's carry, not its ``xs``/``ys``:
    each layer writes its rows into the stack in place, so a step whose
    cache is donated copies no layer of it. A lane at a negative position
    is idle: its row is written as empty, and it is routed to no expert."""
    logits, cache, _ = lm_decode_step_with_fetches(params, tokens, positions,
                                                   cfg, cache, unroll)
    return logits, cache


def lm_decode_step_with_fetches(params, tokens, positions, cfg: ModelConfig,
                                cache, unroll: bool = False):
    """``lm_decode_step``, and per lane the expert weight fetches its
    routed experts made, summed over layers: [B] int32, or None for a
    model without routed experts."""
    x = common.embed(params["embed"], tokens, cfg)
    if cfg.is_encoder_decoder:
        pe = value_of(params["dec_pos_embed"]).astype(x.dtype)
        idx = positions[:, 0] % pe.shape[0]
        x = x + pe[idx][:, None, :]
    enc_out = cache.get("enc_out") if cfg.is_encoder_decoder else None
    # the residual stream in float32 (_apply_block_decode)
    x = constrain(x.astype(jnp.float32), ("batch", None, "act_embed"))

    def body(carry, group_params, layer):
        x, stacks, fetched = carry
        stacks = dict(stacks)
        for i in range(len(cfg.pattern)):
            x, stacks[f"b{i}"], fetches = _apply_block_decode(
                group_params[f"b{i}"], stacks[f"b{i}"], layer, x, positions,
                cfg, i, enc_out=enc_out,
                experts=params["groups"][f"b{i}"].get("moe"))
            if fetches is not None:
                fetched = fetched + fetches
        return x, stacks, fetched

    routed = any(ffn == BlockKind.MOE for _, ffn in cfg.pattern)
    carry = (x, {k: v for k, v in cache.items() if k.startswith("b")},
             jnp.zeros(x.shape[0], jnp.int32) if routed else None)
    if unroll:
        for g in range(cfg.num_groups):
            carry = body(carry, jax.tree.map(lambda a: a[g], params["groups"]),
                         g)
    else:
        carry, _ = jax.lax.scan(
            lambda c, xs: (body(c, *xs), None), carry,
            (params["groups"], jnp.arange(cfg.num_groups, dtype=jnp.int32)))
    x, new_cache, fetched = carry
    if cfg.is_encoder_decoder:
        new_cache["enc_out"] = cache["enc_out"]
    return _logits(params, x, cfg), new_cache, fetched


def lm_append(params, tokens, positions, cfg: ModelConfig, cache):
    """Fold a chunk of k tokens into an existing cache (batched replay).

    tokens [B,k]; positions [B,k] absolute.  Equivalent to k sequential
    lm_decode_step calls up to softmax-reduction order (verified allclose in
    tests); one call amortizes k matmuls into chunk-parallel compute.
    """
    x = common.embed(params["embed"], tokens, cfg)
    if cfg.is_encoder_decoder:
        pe = value_of(params["dec_pos_embed"]).astype(x.dtype)
        x = x + pe[positions % pe.shape[0]]
    enc_out = cache.get("enc_out") if cfg.is_encoder_decoder else None
    x = constrain(x, ("batch", "seq", "act_embed"))

    def body(x, xs):
        group_params, group_cache = xs
        new_caches = {}
        for i in range(len(cfg.pattern)):
            blk = group_params[f"b{i}"]
            mixer, ffn = cfg.pattern[i]
            h = common.rms_norm(x, blk["norm1"], cfg.norm_eps)
            if mixer in MIXERS_WITH_KV:
                local = mixer == BlockKind.ATTN_LOCAL
                y, nc = attention.attn_append(
                    blk["attn"], h, positions, cfg, group_cache[f"b{i}"],
                    local=local)
            elif mixer == BlockKind.RGLRU:
                y, nc = rglru.rglru_block_forward(
                    blk["rglru"], h, cfg, group_cache[f"b{i}"])
            elif mixer == BlockKind.MLSTM:
                y, nc = xlstm.mlstm_block_forward(
                    blk["mlstm"], h, cfg, group_cache[f"b{i}"])
            elif mixer == BlockKind.SLSTM:
                y, nc = xlstm.slstm_block_forward(
                    blk["slstm"], h, cfg, group_cache[f"b{i}"])
            else:
                raise ValueError(mixer)
            x = x + y
            if enc_out is not None and "cross_attn" in blk:
                hc = common.rms_norm(x, blk["norm_cross"], cfg.norm_eps)
                x = x + attention.attn_forward(
                    blk["cross_attn"], hc, positions, cfg, causal=False,
                    kv_x=enc_out)
            if ffn == BlockKind.MLP:
                h2 = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
                x = x + mlp.mlp_forward(blk["mlp"], h2, cfg)
            elif ffn == BlockKind.MOE:
                h2 = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
                y2, _ = moe.moe_forward(blk["moe"], h2, cfg)
                x = x + y2
            new_caches[f"b{i}"] = nc
        return x, new_caches

    layer_cache = {k: v for k, v in cache.items() if k.startswith("b")}
    x, new_layer_cache = jax.lax.scan(body, x, (params["groups"], layer_cache))
    new_cache = dict(new_layer_cache)
    if cfg.is_encoder_decoder:
        new_cache["enc_out"] = cache["enc_out"]
    return _logits(params, x, cfg), new_cache


def lm_prefill(params, batch, cfg: ModelConfig, cache, unroll: bool = False):
    """Process a full prompt, producing logits and a populated cache.

    Implemented as full-sequence attention (flash) plus cache population —
    the KV writes happen layer-by-layer inside the scan.
    """
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, batch, cfg, unroll=unroll)
    x, positions = _embed_inputs(params, batch, cfg)
    if cfg.is_encoder_decoder:
        S = x.shape[1]
        pe = value_of(params["dec_pos_embed"]).astype(x.dtype)
        x = x + pe[jnp.arange(S) % pe.shape[0]][None]

    def body(x, xs):
        group_params, group_cache = xs
        new_caches = {}
        for i in range(len(cfg.pattern)):
            blk = group_params[f"b{i}"]
            mixer, ffn = cfg.pattern[i]
            h = common.rms_norm(x, blk["norm1"], cfg.norm_eps)
            if mixer in MIXERS_WITH_KV:
                local = mixer == BlockKind.ATTN_LOCAL
                y, nc = attention.prefill_into_cache(
                    blk["attn"], h, positions, cfg, group_cache[f"b{i}"],
                    local=local)
            elif mixer == BlockKind.RGLRU:
                y, nc = rglru.rglru_block_forward(
                    blk["rglru"], h, cfg, group_cache[f"b{i}"])
            elif mixer == BlockKind.MLSTM:
                y, nc = xlstm.mlstm_block_forward(
                    blk["mlstm"], h, cfg, group_cache[f"b{i}"])
            elif mixer == BlockKind.SLSTM:
                y, nc = xlstm.slstm_block_forward(
                    blk["slstm"], h, cfg, group_cache[f"b{i}"])
            else:
                raise ValueError(mixer)
            x = x + y
            if enc_out is not None and "cross_attn" in blk:
                hc = common.rms_norm(x, blk["norm_cross"], cfg.norm_eps)
                x = x + attention.attn_forward(
                    blk["cross_attn"], hc, positions, cfg, causal=False,
                    kv_x=enc_out)
            if ffn == BlockKind.MLP:
                h2 = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
                x = x + mlp.mlp_forward(blk["mlp"], h2, cfg)
            elif ffn == BlockKind.MOE:
                h2 = common.rms_norm(x, blk["norm2"], cfg.norm_eps)
                y2, _ = moe.moe_forward(blk["moe"], h2, cfg)
                x = x + y2
            new_caches[f"b{i}"] = nc
        return x, new_caches

    layer_cache = {k: v for k, v in cache.items() if k.startswith("b")}
    x, new_layer_cache = _scan_or_unroll(body, x,
                                         (params["groups"], layer_cache),
                                         unroll)
    new_cache = dict(new_layer_cache)
    if cfg.is_encoder_decoder:
        new_cache["enc_out"] = enc_out
    return _logits(params, x, cfg), new_cache
