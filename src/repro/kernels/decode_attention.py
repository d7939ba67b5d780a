"""Pallas TPU kernel for single-token (decode) attention over a KV cache.

Flash-decode structure: the KV cache's sequence axis is the innermost grid
dim; partial (max, sum, acc) statistics accumulate in VMEM scratch and are
finalized on the last block.  On a seq-sharded cache (logical axis ``kv_seq``
-> mesh ``model``) each shard runs this kernel over its local slice and the
partials combine with an LSE-weighted psum in the ops wrapper.

q [B,1,H,D] is tiny; it is broadcast to every kv block, so the kernel is
purely HBM-bandwidth-bound on the cache — its roofline is bytes(cache)/bw.

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
    qpos_ref,  # SMEM [B] current position of each batch row
    q_ref,  # [H, Hkv*D] block-diagonal query (one batch row)
    k_ref, v_ref,  # [bk, Hkv*D] (kv heads flattened into lanes)
    kpos_ref,  # [1, bk] slot positions (-1 = empty)
    o_ref,  # [H, Hkv*D]
    acc_ref, m_ref, l_ref,  # VMEM scratch [H, Hkv*D], [H, 128], [H, 128]
    *, kv_steps: int, sm_scale: float,
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
    # q is zero outside each head's own kv group, so one 2-D matmul over
    # the flattened kv lanes gives every head's scores against its group
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
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
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [H, Hkv*D]: head h's output is its own group's D lanes
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ik == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, q_pos, k_pos, *, block_k: int = 512,
                     interpret: bool = False):
    """q [B,1,H,D]; caches [B,S,Hkv,D]; q_pos [B]; k_pos [B,S] -> [B,1,H,D].

    The caches enter as ``[B, S, Hkv*D]`` and ``k_pos`` as ``[B, 1, S]``
    (free reshapes), so every block's last two dims are ``(block_k, full)``
    or ``(1, block_k)``: legal on TPU for any batch when ``block_k`` is a
    multiple of 128 or all of ``S``.  GQA runs as 2-D matmuls against a
    block-diagonal query; the wrapper keeps each head's own group."""
    B, _, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    sm_scale = float(1.0 / (D ** 0.5))
    group = jnp.arange(H) // g
    own = (group[:, None] == jnp.arange(Hkv)[None, :]).astype(q.dtype)
    q_bd = (q[:, 0, :, None, :] * own[None, :, :, None]).reshape(
        B, H, Hkv * D)
    kv_spec = pl.BlockSpec((None, block_k, Hkv * D), lambda b, ik: (b, ik, 0))
    row_spec = pl.BlockSpec((None, H, Hkv * D), lambda b, ik: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, kv_steps=nk, sm_scale=sm_scale),
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            row_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((None, 1, block_k), lambda b, ik: (b, 0, ik)),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Hkv * D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, Hkv * D), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), q_bd, k_cache.reshape(B, S, Hkv * D),
      v_cache.reshape(B, S, Hkv * D), k_pos.astype(jnp.int32).reshape(B, 1, S))
    out = out.reshape(B, H, Hkv, D)[:, jnp.arange(H), group]  # [B, H, D]
    return out[:, None]
