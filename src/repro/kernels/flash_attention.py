"""Pallas TPU flash attention (prefill/train) with causal + local-window
masking and GQA, tiled for VMEM.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv-block axis is the
innermost ("arbitrary") dimension, accumulating the online softmax in VMEM
scratch (acc/m/l).  Block shapes are MXU-aligned (multiples of 128 on the
contracting/lane dims; head_dim in {64,128,256} for all ten archs).

Causal/local skipping: kv blocks strictly above the causal diagonal (or
outside the window band) contribute nothing; their compute is skipped with
``@pl.when``, so the kernel does ~S*W work for local attention and ~S^2/2
for causal — the quantity the roofline compute term credits.

Validated in interpret mode against ``ref.naive_attention``
(tests/test_kernels.py sweeps shapes x dtypes x window settings).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

NEG_INF = _ref.NEG_INF


def _attn_kernel(
    q_ref, k_ref, v_ref,  # [1, 1, bq, D], [1, 1, bk, D] x2
    o_ref,  # [1, 1, bq, D]
    acc_ref, m_ref, l_ref,  # VMEM scratch: [bq, D] f32, [bq, 128], [bq, 128]
    *, causal: bool, window: int, block_q: int, block_k: int, sm_scale: float,
    kv_steps: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Static-shape mask bounds: a kv block participates unless fully masked.
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]  # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    # Skip kv blocks that are fully masked (beyond causal diagonal or
    # outside the local window band).
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window > 0:
        run &= k_start + block_k - 1 > q_start - window

    @pl.when(run)
    def _():
        _compute()

    @pl.when(ik == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, window: int = 0,
    block_q: int = 512, block_k: int = 512, interpret: bool = False,
):
    """q [B,Sq,H,D]; k/v [B,Sk,Hkv,D] -> [B,Sq,H,D].  GQA via index_map."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    sm_scale = float(1.0 / (D ** 0.5))

    # layout: heads as a grid axis; blocks [1,1,bq,D] so the lane dim is D.
    qt = q.transpose(0, 2, 1, 3)  # [B,H,Sq,D]
    kt = k.transpose(0, 2, 1, 3)  # [B,Hkv,Sk,D]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel,
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        sm_scale=sm_scale, kv_steps=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
