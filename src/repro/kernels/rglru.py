"""Pallas TPU kernel for the RG-LRU recurrence (recurrentgemma / griffin).

The recurrence is elementwise over the width dim (no matmul): it is purely
memory-bound, so the kernel's job is to stream x/gates through VMEM once,
keeping the hidden state resident in VMEM scratch across sequence chunks.

Grid: (batch, width_blocks, seq_chunks); seq is the innermost arbitrary dim.
Within a chunk the timestep loop is a ``fori_loop`` over VPU-width rows —
the same structure as the reference recurrentgemma Pallas kernel.

Validated in interpret mode against ``ref.naive_rglru``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(
    x_ref, ga_ref, gx_ref, a_ref,  # [1, T, Wb], [1, T, Wb], [1, T, Wb], [1, Wb]
    h0_ref,  # [1, Wb] initial state (chunk 0 only)
    out_ref,  # [1, T, Wb]
    hlast_ref,  # [1, Wb]
    h_scratch,  # VMEM [1, Wb] f32
    *, c: float, chunk: int, n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scratch[...] = h0_ref[...].astype(jnp.float32)

    log_a = jax.nn.log_sigmoid(a_ref[0].astype(jnp.float32))  # [Wb]
    r = jax.nn.sigmoid(ga_ref[0].astype(jnp.float32))  # [T, Wb]
    i = jax.nn.sigmoid(gx_ref[0].astype(jnp.float32))
    log_at = c * r * log_a[None, :]
    a_t = jnp.exp(log_at)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 1e-12))
    gated = beta * (i * x_ref[0].astype(jnp.float32))

    def step(t, h):
        h = a_t[t] * h + gated[t]
        out_ref[0, t, :] = h.astype(out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scratch[0, :])
    h_scratch[0, :] = h

    @pl.when(ic == n_chunks - 1)
    def _finish():
        hlast_ref[...] = h_scratch[...]


@functools.partial(
    jax.jit, static_argnames=("c", "block_w", "chunk", "interpret")
)
def rglru(x, a_param, gate_a, gate_x, h0=None, *, c: float = 8.0,
          block_w: int = 512, chunk: int = 256, interpret: bool = False):
    """x/gates [B,S,W]; a_param [W]; h0 [B,W] -> (h_seq [B,S,W], h_last [B,W])."""
    B, S, W = x.shape
    block_w = min(block_w, W)
    chunk = min(chunk, S)
    assert W % block_w == 0 and S % chunk == 0, (W, block_w, S, chunk)
    nw, nc = W // block_w, S // chunk
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    a2d = jnp.broadcast_to(a_param[None, :], (B, W))

    out, hlast = pl.pallas_call(
        functools.partial(_rglru_kernel, c=c, chunk=chunk, n_chunks=nc),
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, s: (b, s, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, s: (b, s, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, s: (b, s, w)),
            pl.BlockSpec((1, block_w), lambda b, w, s: (b, w)),
            pl.BlockSpec((1, block_w), lambda b, w, s: (b, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, s: (b, s, w)),
            pl.BlockSpec((1, block_w), lambda b, w, s: (b, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, gate_a, gate_x, a2d, h0)
    return out, hlast
