"""Mixture-of-Experts FFN with capacity-bounded sort-based dispatch.

Design goals (in roofline order):
  1. HLO FLOPs must track *active* parameters — so dispatch/combine are
     gathers/scatters (byte traffic, ~zero FLOPs), and expert compute is a
     single [E,C,D]x[E,D,F] batched einsum whose FLOPs = capacity-bounded
     active compute.  The dense one-hot-einsum dispatch used by early
     Switch implementations costs O(T^2 D) FLOPs and would poison the
     MODEL_FLOPS/HLO_FLOPs ratio.
  2. Experts shard over the ``model`` mesh axis (expert parallelism); token
     buffers get an explicit sharding constraint so dispatch lowers to an
     all-to-all-shaped exchange rather than full replication.

Routing: top-k softmax gating with a Switch-style load-balancing auxiliary
loss and capacity factor; overflowing tokens drop (their residual passes
through — standard behaviour).

Decode (one token per lane, ``moe_decode``) routes the same way but runs
no capacity dispatch: each live lane's token goes through its top-k
experts alone, and only those experts' weights are read
(``kernels/moe_decode.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.common import param, value_of
from repro.models import mlp as _mlp
from repro.sharding.rules import with_sharding_constraint_logical as constrain


def init_moe(key, cfg):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": param(ks[0], (d, E), ("embed", "experts"), scale=0.02),
        "w_gate": param(ks[1], (E, d, ff), ("experts", "embed", "expert_mlp")),
        "w_up": param(ks[2], (E, d, ff), ("experts", "embed", "expert_mlp")),
        "w_down": param(ks[3], (E, ff, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.shared_expert:
        p["shared"] = _mlp.init_mlp(ks[4], cfg)
    return p


def expert_capacity(cfg, tokens: int) -> int:
    cap = int(tokens * cfg.num_experts_per_tok * cfg.capacity_factor
              / cfg.num_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8


def moe_forward(params, x, cfg):
    """x [B,S,D] -> (out [B,S,D], aux_loss scalar)."""
    if cfg.moe_routing == "local":
        return _moe_forward_local(params, x, cfg)
    return _moe_forward_global(params, x, cfg)


def _expert_axes(cfg):
    return "act_experts" if cfg.expert_sharding == "model" else None


def _router(params, xf, cfg):
    """shared: logits/top-k/aux over a flat token dim (batched or global)."""
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = (xf @ value_of(params["router"]).astype(xf.dtype)).astype(jnp.float32)
    probs, gate_w, gate_ids = top_k_gates(logits, K)
    one_hot_top1 = jax.nn.one_hot(gate_ids[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(
        jnp.mean(one_hot_top1.reshape(-1, E), 0)
        * jnp.mean(probs.reshape(-1, E), 0))
    aux = aux + 1e-3 * jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return gate_w, gate_ids, aux


def top_k_gates(logits, k: int):
    """Router logits ``[..., E]`` -> (softmax probabilities, the ``k``
    largest renormalised to sum to one, their expert ids)."""
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_ids = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return probs, gate_w, gate_ids


def moe_decode(params, x, cfg, live, experts=None, layer=0):
    """One decode token per lane through only the experts it routes to.

    x [B,1,D], the normed input; ``live`` [B] bool: an idle lane is
    routed to nothing and gets zero. ``experts`` is the decode step's stack
    of this block's expert weights (``[L, E, ...]``), read at ``layer`` in
    place; without it, ``params``' own. Returns (out [B,1,D] float32,
    unrounded for the float32 residual stream it joins, fetches [B] int32:
    per lane, the expert weight fetches opened for it).

    The router reads x unrounded, in float32 at full precision (the
    selective precision of Switch Transformers): its logits rounded to
    bfloat16 are 0.004-0.008 apart at a spread of about 0.6, against a
    typical 0.06 between the k-th and the next of 32 experts, so bfloat16
    breaks near ties otherwise than the model does, and one swapped expert
    moves the residual stream by several per cent. The product is
    ``[B, D] x [D, E]``: its cost does not show in the step. The experts
    read x in the compute dtype."""
    logits = jnp.dot(x[:, 0].astype(jnp.float32),
                     value_of(params["router"]).astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, gate_w, gate_ids = top_k_gates(logits, cfg.num_experts_per_tok)
    x = x.astype(cfg.compute_dtype)
    w = params if experts is None else experts
    out, fetches = ops.moe_decode(
        x[:, 0], gate_w, gate_ids, live, value_of(w["w_gate"]),
        value_of(w["w_up"]), value_of(w["w_down"]), layer)
    out = out[:, None]
    if cfg.shared_expert:
        out = out + _mlp.mlp_forward(params["shared"], x, cfg)
    return out, fetches


def _moe_forward_local(params, x, cfg):
    """Grouped local routing, formulated scatter-free.

    Every bookkeeping op is batched over the batch-row axis (sharded over
    `data`) and is either a local sort or a ``take_along_axis`` gather —
    the only batched-index forms the SPMD partitioner keeps collective-free
    (measured: advanced-index gathers and every scatter form insert
    all-gathers/all-reduces/permute pipelines; see EXPERIMENTS.md §Perf A).

      dispatch: entries sorted by expert are contiguous runs; slot (e,c)
                reads entry ``starts[e]+c`` — a gather, not a scatter.
      combine:  un-sort by the inverse permutation and sum the K expert
                contributions per token — reshape+sum, not a scatter.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = expert_capacity(cfg, S)  # per-row capacity
    dt = x.dtype
    eax = _expert_axes(cfg)

    gate_w, gate_ids, aux = _router(params, x, cfg)  # [B,S,K]

    flat_e = gate_ids.reshape(B, S * K)
    order = jnp.argsort(flat_e, axis=-1, stable=True)  # per-row sort: local
    inv_order = jnp.argsort(order, axis=-1)  # inverse permutation
    e_s = jnp.take_along_axis(flat_e, order, axis=-1)
    t_s = order // K  # token id of sorted entry (entries are token-major)

    # run starts per expert: starts[b,e] = first sorted index of expert e
    starts = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E), side="left"))(e_s)

    # ---- dispatch as a gather: slot (e,c) <- sorted entry starts[e]+c ----
    src = (starts[:, :, None] + jnp.arange(C)[None, None, :])  # [B,E,C]
    src_flat = src.reshape(B, E * C)
    in_range = src_flat < S * K
    src_safe = jnp.minimum(src_flat, S * K - 1)
    e_at_src = jnp.take_along_axis(e_s, src_safe, axis=1)
    hit = in_range & (e_at_src == (jnp.arange(E * C)[None] // C))
    tok = jnp.take_along_axis(t_s, src_safe, axis=1)  # [B,E*C]
    gathered = jnp.take_along_axis(
        x, jnp.where(hit, tok, 0)[..., None], axis=1)
    expert_in = (gathered * hit[..., None].astype(dt)).reshape(B, E, C, D)
    expert_in = constrain(expert_in, ("batch", eax, None, None))

    wg = value_of(params["w_gate"]).astype(dt)
    wu = value_of(params["w_up"]).astype(dt)
    wd = value_of(params["w_down"]).astype(dt)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in, wg))
    h = h * jnp.einsum("becd,edf->becf", expert_in, wu)
    h = constrain(h, ("batch", eax, None, None))
    expert_out = jnp.einsum("becf,efd->becd", h, wd)
    expert_out = constrain(expert_out, ("batch", eax, None, None))

    # ---- combine as a gather: sorted entry i sits at slot e_s*C + rank ----
    flat_out = expert_out.reshape(B, E * C, D)
    rank = jnp.arange(S * K)[None] - jnp.take_along_axis(starts, e_s, axis=1)
    kept = rank < C  # capacity overflow drops (token keeps its residual)
    slot = jnp.where(kept, e_s * C + rank, 0)
    per_entry = jnp.take_along_axis(flat_out, slot[..., None], axis=1)
    per_entry = per_entry * kept[..., None].astype(dt)
    # un-sort back to token-major order and fold the K contributions
    unsorted = jnp.take_along_axis(per_entry, inv_order[..., None], axis=1)
    w = gate_w.reshape(B, S, K).astype(dt)
    out = jnp.einsum("bskd,bsk->bsd", unsorted.reshape(B, S, K, D), w)

    if cfg.shared_expert:
        out = out + _mlp.mlp_forward(params["shared"], x, cfg)
    return constrain(out, ("batch", "seq", "act_embed")), aux


def _moe_forward_global(params, x, cfg):
    """Baseline: one global token pool (global sort/scatter)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    C = expert_capacity(cfg, T)
    dt = x.dtype
    xf = x.reshape(T, D)

    logits = (xf @ value_of(params["router"]).astype(dt)).astype(jnp.float32)
    probs, gate_w, gate_ids = top_k_gates(logits, K)  # [T,E], [T,K], [T,K]

    # Switch load-balance loss: E * mean(frac_tokens_e * mean_prob_e)
    one_hot_top1 = jax.nn.one_hot(gate_ids[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(one_hot_top1, 0) * jnp.mean(probs, 0))
    # router z-loss (stabilizes logits)
    aux = aux + 1e-3 * jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)

    # ---- sort-based dispatch ----
    flat_e = gate_ids.reshape(-1)  # [T*K]
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    e_s, t_s, w_s = flat_e[order], flat_t[order], flat_w[order]
    seg_start = jnp.searchsorted(e_s, e_s, side="left")
    pos = jnp.arange(T * K) - seg_start  # rank within expert
    keep = pos < C
    slot = jnp.where(keep, e_s * C + pos, E * C)  # E*C = dump slot

    gathered = jnp.take(xf, t_s, axis=0) * keep[:, None].astype(dt)  # [T*K, D]
    buf = jnp.zeros((E * C + 1, D), dt).at[slot].add(gathered)
    expert_in = buf[: E * C].reshape(E, C, D)
    expert_in = constrain(expert_in, ("act_experts", None, None))

    wg = value_of(params["w_gate"]).astype(dt)
    wu = value_of(params["w_up"]).astype(dt)
    wd = value_of(params["w_down"]).astype(dt)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, wu)
    h = constrain(h, ("act_experts", None, None))
    expert_out = jnp.einsum("ecf,efd->ecd", h, wd)
    expert_out = constrain(expert_out, ("act_experts", None, None))

    # ---- combine ----
    flat_out = expert_out.reshape(E * C, D)
    vals = jnp.take(flat_out, jnp.minimum(slot, E * C - 1), axis=0)
    vals = vals * (w_s * keep).astype(dt)[:, None]
    out = jnp.zeros((T, D), dt).at[t_s].add(vals)

    if cfg.shared_expert:
        out = out + _mlp.mlp_forward(params["shared"], x, cfg).reshape(T, D)
    out = out.reshape(B, S, D)
    return constrain(out, ("batch", "seq", "act_embed")), aux
