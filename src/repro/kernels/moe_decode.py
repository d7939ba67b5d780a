"""Pallas TPU kernel for the routed experts of a decode step.

At decode each lane brings one token, routed to ``k`` of ``E`` experts.
The capacity dispatch of ``models/moe.py`` (training and prefill) gives
every expert a buffer of slots and multiplies all of them, so it reads
every expert's weights in every step; a step of ``B`` lanes needs at most
``B * k`` of them. This kernel reads only the experts the live lanes
route to, each once.

The wrapper lists the step's ``(lane, k)`` pairs sorted by expert, the
pairs of live lanes first (``route``). The pairs' experts, lanes and
liveness are prefetched scalars, and each weight's ``index_map`` puts the
pair's expert on the expert axis. Pallas fetches a block only when its
index differs from the grid step before, so consecutive pairs on one
expert share one fetch: each distinct routed expert is read once per
call. A dead pair (an idle lane's) keeps the block of the last live pair
before it, fetches nothing and writes zeros. The weights may be the decode
step's stacked ``[L, E, ...]`` leaves, read at a prefetched layer in place
(as ``decode_attention`` reads its cache), so the step copies no expert.

Grid step ``p`` runs pair ``p``'s expert over every lane's row (the MXU
takes eight rows for the price of one) and keeps its own lane's row; the
wrapper weights each pair by its gate and sums a lane's pairs in float32.
With no live lane at all the first grid step still fetches one expert's
blocks (an engine never steps with every lane idle).

Validated in interpret mode against ``ref.naive_moe_decode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def route(gate_ids, live):
    """The step's ``(lane, k)`` pairs in the kernel's order: sorted by
    expert, live lanes' pairs first. Returns ``(order, expert, lane, on)``,
    each ``[B*K]``: ``order`` indexes the lane-major pairs, ``on`` is 1 for
    a live lane's pair, and a dead pair takes the expert of the last live
    pair before it, so that its block index does not change."""
    B, K = gate_ids.shape
    on = jnp.repeat(live.astype(jnp.int32), K)
    key = jnp.where(on == 1, gate_ids.reshape(-1), jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, stable=True)
    expert = gate_ids.reshape(-1)[order].astype(jnp.int32)
    on = on[order]
    last_live = expert[jnp.maximum(jnp.sum(on) - 1, 0)]
    expert = jnp.where(on == 1, expert, last_live)
    lane = (order // K).astype(jnp.int32)
    return order, expert, lane, on


def fetches(gate_ids, live):
    """``[B]`` int32: per lane, the expert weight fetches that ``route``'s
    order opens for it (a live pair whose expert differs from the pair
    before). Their sum is the distinct experts the live lanes route to."""
    _, expert, lane, on = route(gate_ids, live)
    new = jnp.concatenate([jnp.ones((1,), bool), expert[1:] != expert[:-1]])
    opened = (new & (on == 1)).astype(jnp.int32)
    return jnp.zeros(gate_ids.shape[0], jnp.int32).at[lane].add(opened)


def _moe_kernel(layer_ref, expert_ref, lane_ref, on_ref,  # SMEM scalars
                x_ref,          # [Bp, D] every lane's row
                wg_ref, wu_ref,  # [D, F] the pair's expert
                wd_ref,         # [F, D]
                o_ref):         # [1, D] the pair's output (float32)
    p = pl.program_id(0)

    @pl.when(on_ref[p] == 1)
    def _expert():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        o_ref[...] = jnp.sum(jnp.where(rows == lane_ref[p], y, 0.0), axis=0,
                             keepdims=True)

    @pl.when(on_ref[p] == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_decode(x, gate_w, gate_ids, live, w_gate, w_up, w_down, layer=0, *,
               interpret: bool = False):
    """x [B, D]; gate_w, gate_ids [B, K] (gates renormalised over the
    top k); live [B] bool; w_gate, w_up [E, D, F] and w_down [E, F, D], or
    stacked [L, E, ...] and read at ``layer`` -> [B, D] float32:
    ``sum_k gate_k * W_down[e_k](silu(x W_gate[e_k]) * x W_up[e_k])`` for a
    live lane, zero for an idle one.

    Each weight block is an expert's whole ``[D, F]`` or ``[F, D]``
    matrix, whose last two dims are the array's: legal on a TPU at any
    width. The rows are padded to the sublane tile of 8."""
    if w_gate.ndim == 3:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
    B, D = x.shape
    K = gate_ids.shape[1]
    F = w_gate.shape[-1]
    P = B * K
    order, expert, lane, on = route(gate_ids, live)
    Bp = -(-B // 8) * 8
    xp = jnp.pad(x.astype(w_gate.dtype), ((0, Bp - B), (0, 0)))

    def weights(rows, cols):
        return pl.BlockSpec((None, None, rows, cols),
                            lambda p, layer, expert, lane, on:
                            (layer[0], expert[p], 0, 0))

    pairs = pl.pallas_call(
        _moe_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(P,),
            in_specs=[
                pl.BlockSpec((Bp, D), lambda p, *_: (0, 0)),
                weights(D, F),
                weights(D, F),
                weights(F, D),
            ],
            out_specs=pl.BlockSpec((None, 1, D), lambda p, *_: (p, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((P, 1, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), expert, lane, on, xp,
      w_gate, w_up, w_down)
    # back to lane-major pairs, then each lane's gated sum over its k
    per_pair = pairs[jnp.argsort(order), 0]
    gates = gate_w.astype(jnp.float32) * live[:, None]
    return jnp.sum(per_pair.reshape(B, K, D) * gates[..., None], axis=1)
