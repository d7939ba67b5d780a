"""Pallas TPU kernel for the sLSTM recurrence (xLSTM §2.2).

sLSTM is a true recurrence (h_{t-1} feeds the gates), so time is
sequential; the exploitable parallelism is the *block-diagonal per-head*
structure: head h's state never mixes with head h'.  Grid:
(batch, heads, seq_chunks) — heads are an embarrassingly parallel grid dim,
seq chunks are the arbitrary dim carrying (c, n, h, m) in VMEM scratch;
each timestep does a [1,hb]x[hb,hb] MXU matvec per gate.

Validated in interpret mode against ``ref.naive_slstm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_kernel(
    xi_ref, xf_ref, xz_ref, xo_ref,  # [1, T, 1, hb]
    ri_ref, rf_ref, rz_ref, ro_ref,  # [1, hb, hb]
    h_out_ref,  # [1, T, 1, hb]
    c_ref, n_ref, h_ref, m_ref,  # VMEM scratch [1, hb] f32
    *, chunk: int, n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.ones_like(n_ref)
        h_ref[...] = jnp.zeros_like(h_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    ri = ri_ref[0].astype(jnp.float32)  # [hb, hb]
    rf = rf_ref[0].astype(jnp.float32)
    rz = rz_ref[0].astype(jnp.float32)
    ro = ro_ref[0].astype(jnp.float32)

    def step(t, carry):
        c, n, h, m = carry

        def z(x_ref, r):
            return (x_ref[0, t, 0].astype(jnp.float32)
                    + jnp.dot(h[0], r, preferred_element_type=jnp.float32))

        zi = z(xi_ref, ri)[None, :]
        zf = z(xf_ref, rf)[None, :]
        zz = z(xz_ref, rz)[None, :]
        zo = z(xo_ref, ro)[None, :]
        m_new = jnp.maximum(zf + m, zi)
        ie = jnp.exp(zi - m_new)
        fe = jnp.exp(zf + m - m_new)
        c = fe * c + ie * jnp.tanh(zz)
        n = fe * n + ie
        h = jax.nn.sigmoid(zo) * c / jnp.maximum(n, 1e-6)
        h_out_ref[0, t, 0, :] = h[0].astype(h_out_ref.dtype)
        return (c, n, h, m_new)

    c, n, h, m = jax.lax.fori_loop(
        0, chunk, step, (c_ref[...], n_ref[...], h_ref[...], m_ref[...]))
    c_ref[...], n_ref[...], h_ref[...], m_ref[...] = c, n, h, m


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def slstm(x_i, x_f, x_z, x_o, r_i, r_f, r_z, r_o, *, chunk: int = 128,
          interpret: bool = False):
    """x_* [B,S,W]; r_* [H,hb,hb] -> h_seq [B,S,W] (fresh state)."""
    B, S, W = x_i.shape
    H, hb = r_i.shape[0], r_i.shape[1]
    assert H * hb == W
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    xs = [x.reshape(B, S, H, hb) for x in (x_i, x_f, x_z, x_o)]

    out = pl.pallas_call(
        functools.partial(_slstm_kernel, chunk=chunk, n_chunks=nc),
        grid=(B, H, nc),
        in_specs=[
            *[pl.BlockSpec((1, chunk, 1, hb), lambda b, h, c: (b, c, h, 0))
              for _ in range(4)],
            *[pl.BlockSpec((1, hb, hb), lambda b, h, c: (h, 0, 0))
              for _ in range(4)],
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, hb), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, hb), x_i.dtype),
        scratch_shapes=[pltpu.VMEM((1, hb), jnp.float32) for _ in range(4)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*xs, r_i, r_f, r_z, r_o)
    return out.reshape(B, S, W)
