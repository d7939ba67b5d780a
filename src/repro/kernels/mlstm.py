"""Pallas TPU kernel for the mLSTM: chunkwise-parallel form (TFLA-style).

The recurrent form (ref.naive_mlstm) is a strict scan — VPU-bound, O(D^2)
elementwise work per step.  The chunkwise form turns a T-step chunk into
MXU matmuls:

  intra-chunk:  S[t,j] = (q_t.k_j/sqrt(D)) * exp(b_t - b_j + logi_j - m_t)
                for j <= t   (one [T,T] masked matmul + one [T,T]x[T,D])
  inter-chunk:  exp(b_t + m_in - m_t) * (q_t @ C_in)   ([T,D]x[D,D])
  state update: C_out = exp(F + m_in - m_out) C_in
                + sum_j exp(F - b_j + logi_j - m_out) v_j k_j^T ([D,T]x[T,D])

with b = inclusive cumsum(logf), F = b[-1]; the running stabilizer
m_t = max(b_t + m_in, max_{j<=t}(b_t - b_j + logi_j)) is *identical* to the
sequential form's, so the kernel matches ref.naive_mlstm to float tolerance.

Grid: (batch, heads, chunks); chunks is the arbitrary dim carrying
(C [D,D], n [D], m [1]) in VMEM scratch.

Validated in interpret mode against ``ref.naive_mlstm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF


def _mlstm_kernel(
    q_ref, k_ref, v_ref,  # [1, 1, T, D]
    i_ref, f_ref,  # [1, 1, T, 128] (gate pre-activations, lane-padded)
    h_ref,  # out [1, 1, T, D]
    c_ref, n_ref, m_ref,  # VMEM scratch: [D, D] f32, [1, D] f32, [1, 128] f32
    *, chunk: int, n_chunks: int, sm_scale: float,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    T = chunk
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [T, D]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    logi = i_ref[0, 0, :, 0].astype(jnp.float32)  # [T]
    logf = jax.nn.log_sigmoid(f_ref[0, 0, :, 0].astype(jnp.float32))

    b = jnp.cumsum(logf)  # inclusive [T]
    F = b[T - 1]
    m_in = m_ref[0, 0]

    # stabilizer: m_t = max(b_t + m_in, max_{j<=t}(b_t - b_j + logi_j))
    tri = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
    intra_log = b[:, None] - b[None, :] + logi[None, :]  # [T,T] (t,j)
    intra_log = jnp.where(tri, intra_log, NEG_INF)
    m_t = jnp.maximum(b + m_in, jnp.max(intra_log, axis=1))  # [T]

    # intra attention matrix
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [T,T]
    S = qk * jnp.exp(intra_log - m_t[:, None])
    S = jnp.where(tri, S, 0.0)

    inter_scale = jnp.exp(b + m_in - m_t)  # [T]
    # C is [Dv, Dk]; q contracts with the k-axis: qc[t, dv] = sum_dk q C^T
    qc = jax.lax.dot_general(q, c_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [T,Dv]
    num = jax.lax.dot_general(S, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32) \
        + inter_scale[:, None] * qc
    qn = jax.lax.dot_general(q, n_ref[0][:, None], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)[:, 0]  # [T]
    den = jnp.sum(S, axis=1) + inter_scale * qn
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    h_ref[0, 0] = (num / den[:, None]).astype(h_ref.dtype)

    # state update
    m_out = jnp.maximum(F + m_in, jnp.max(F - b + logi))
    w = jnp.exp(F - b + logi - m_out)  # [T]
    kv = jax.lax.dot_general(v * w[:, None], k, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Dv,Dk]
    c_ref[...] = jnp.exp(F + m_in - m_out) * c_ref[...] + kv
    n_ref[0] = jnp.exp(F + m_in - m_out) * n_ref[0] + jnp.sum(
        w[:, None] * k, axis=0)
    m_ref[0, 0] = m_out


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm(q, k, v, i_gate, f_gate, *, chunk: int = 128,
          interpret: bool = False):
    """q/k/v [B,S,H,D]; i_gate/f_gate [B,S,H] -> h [B,S,H,D].

    C[b,h] is [Dv,Dk]: rows index v-dims, cols index k-dims, matching
    ref.naive_mlstm's C[b,h,dv,dk].
    """
    B, S, H, D = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    sm_scale = float(1.0 / (D ** 0.5))
    qt = q.transpose(0, 2, 1, 3)  # [B,H,S,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # gates [B,S,H] -> [B,H,S,128] (lane-pad so the trailing dim is tiled)
    ig = jnp.broadcast_to(i_gate.transpose(0, 2, 1)[..., None],
                          (B, H, S, 128))
    fg = jnp.broadcast_to(f_gate.transpose(0, 2, 1)[..., None],
                          (B, H, S, 128))

    out = pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk=chunk, n_chunks=nc,
                          sm_scale=sm_scale),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 128), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 128), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((D, D), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qt, kt, vt, ig, fg)
    return out.transpose(0, 2, 1, 3)
