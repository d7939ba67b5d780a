"""Pallas TPU kernel for single-token (decode) attention over a KV cache.

Flash-decode structure: the KV cache's sequence axis is the innermost grid
dim; partial (max, sum, acc) statistics accumulate in VMEM scratch and are
finalized on the last block.  On a seq-sharded cache (logical axis ``kv_seq``
-> mesh ``model``) each shard runs this kernel over its local slice and the
partials combine with an LSE-weighted psum in the ops wrapper.

q [B,1,H,D] is tiny; it is broadcast to every kv block, so the kernel is
purely HBM-bandwidth-bound on the cache — its roofline is bytes(cache)/bw.

The caches may be the decode step's stacked ``[L,B,S,Hkv,D]`` leaves: the
layer index is a prefetched scalar that the K/V ``index_map`` puts on the
layer axis, so the kernel reads one layer in place and the step never
copies a layer out of the stack.  It reads K/V blocks either as
``[bk, Hkv*D]`` (``seq_minor=False``) or as ``[Hkv*D, bk]``
(``seq_minor=True``): the latter is how a TPU lays out a cache whose head
size is narrower than its 128 lanes (sequence axis minor-most), so that
view of the stack is the same bytes and costs no relayout.

Validated in interpret mode against ``ref.decode_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF


def _decode_kernel(
    layer_ref,  # SMEM [1] layer of the stacked caches (read by index_map)
    qpos_ref,  # SMEM [B] current position of each batch row
    q_ref,  # [H, Hkv*D] block-diagonal query (one batch row)
    k_ref, v_ref,  # [bk, Hkv*D], or [Hkv*D, bk] when seq_minor
    kpos_ref,  # [1, bk] slot positions (-1 = empty)
    o_ref,  # [H, Hkv*D]
    acc_ref, m_ref, l_ref,  # VMEM scratch [H, Hkv*D], [H, 128], [H, 128]
    *, kv_steps: int, sm_scale: float, seq_minor: bool,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * sm_scale
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    # the contracted axis of k and v: their kv lanes, then their positions
    kv_lanes, kv_pos = (0, 1) if seq_minor else (1, 0)
    # q is zero outside each head's own kv group, so one 2-D matmul over
    # the flattened kv lanes gives every head's scores against its group
    s = jax.lax.dot_general(
        q, k, (((1,), (kv_lanes,)), ((), ())),
        preferred_element_type=jnp.float32
    )  # [H, bk]
    kpos = kpos_ref[...]  # [1, bk]
    valid = (kpos >= 0) & (kpos <= qpos_ref[pl.program_id(0)])
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
    m_ref[:, 0] = m_new
    pv = jax.lax.dot_general(
        p, v, (((1,), (kv_pos,)), ((), ())),
        preferred_element_type=jnp.float32
    )  # [H, Hkv*D]: head h's output is its own group's D lanes
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ik == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("seq_minor", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, q_pos, k_pos, layer=0, *,
                     seq_minor: bool = False, block_k: int = 512,
                     interpret: bool = False):
    """q [B,1,H,D]; caches [B,S,Hkv,D], or stacked [L,B,S,Hkv,D] read at
    ``layer``; q_pos [B]; k_pos [B,S] -> [B,1,H,D].

    The caches enter as ``[L, B, S, Hkv*D]``, or ``[L, B, Hkv*D, S]`` when
    ``seq_minor``, and ``k_pos`` as ``[B, 1, S]`` (free reshapes in the
    matching device layout), so every block's last two dims are
    ``(block_k, full)``, ``(full, block_k)`` or ``(1, block_k)``: legal on
    TPU for any batch when ``block_k`` is a multiple of 128 or all of
    ``S``.  GQA runs as 2-D matmuls against a block-diagonal query; the
    wrapper keeps each head's own group."""
    if k_cache.ndim == 4:
        k_cache, v_cache = k_cache[None], v_cache[None]
    B, _, H, D = q.shape
    L, _, S, Hkv = k_cache.shape[:4]
    g = H // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    sm_scale = float(1.0 / (D ** 0.5))
    group = jnp.arange(H) // g
    own = (group[:, None] == jnp.arange(Hkv)[None, :]).astype(q.dtype)
    q_bd = (q[:, 0, :, None, :] * own[None, :, :, None]).reshape(
        B, H, Hkv * D)
    if seq_minor:
        view = lambda c: c.transpose(0, 1, 3, 4, 2).reshape(L, B, Hkv * D, S)
        kv_spec = pl.BlockSpec((None, None, Hkv * D, block_k),
                               lambda b, ik, layer, qpos: (layer[0], b, 0, ik))
    else:
        view = lambda c: c.reshape(L, B, S, Hkv * D)
        kv_spec = pl.BlockSpec((None, None, block_k, Hkv * D),
                               lambda b, ik, layer, qpos: (layer[0], b, ik, 0))
    row_spec = pl.BlockSpec((None, H, Hkv * D),
                            lambda b, ik, layer, qpos: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, kv_steps=nk, sm_scale=sm_scale,
                          seq_minor=seq_minor),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk),
            in_specs=[
                row_spec,
                kv_spec,
                kv_spec,
                pl.BlockSpec((None, 1, block_k),
                             lambda b, ik, layer, qpos: (b, 0, ik)),
            ],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((H, Hkv * D), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Hkv * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q_pos.astype(jnp.int32),
      q_bd, view(k_cache), view(v_cache),
      k_pos.astype(jnp.int32).reshape(B, 1, S))
    out = out.reshape(B, H, Hkv, D)[:, jnp.arange(H), group]  # [B, H, D]
    return out[:, None]
