"""Pure-jnp oracles for every Pallas kernel, plus blockwise ("flash") jnp
implementations used as the lowering path on non-TPU backends.

Three tiers per op:
  * ``naive_*``      — simplest possible semantics; ground truth in tests.
  * ``blockwise_*``  — lax.scan online-softmax/linear-scan formulations whose
                       HLO working set matches the TPU kernel's VMEM tiling
                       (so the CPU dry-run's memory roofline term is honest).
  * the Pallas kernel (sibling modules) — the TPU target, validated in
                       interpret mode against ``naive_*``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# attention oracles
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, *, causal=True, window=0, q_pos=None, k_pos=None):
    """Full-materialization GQA attention.  q [B,Sq,H,D]; k/v [B,Sk,Hkv,D].

    ``window`` > 0 limits keys to (q_pos - window, q_pos].  ``q_pos``/``k_pos``
    default to arange (prefill); decode passes explicit positions.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if k_pos is None:
        k_pos = jnp.arange(Sk)
    qg = q.reshape(B, Sq, Hkv, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window and window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def blockwise_attention(
    q, k, v, *, causal=True, window=0, block_k: int = 1024,
    q_pos=None, k_pos=None,
):
    """Online-softmax attention, scanning KV in blocks (flash formulation).

    Never materializes [Sq, Sk]; the per-step working set is [.., Sq, block_k],
    mirroring the Pallas kernel's VMEM tile.  Used for train/prefill lowering
    on CPU and as a second oracle for the kernel.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if k_pos is None:
        k_pos = jnp.arange(Sk)
    block_k = min(block_k, Sk)
    pad = (-Sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-10**9)
    nb = (Sk + pad) // block_k
    qg = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, g, D)

    def body(carry, start):
        acc, m, l = carry
        kb = jax.lax.dynamic_slice_in_dim(k, start, block_k, axis=1).astype(jnp.float32)
        vb = jax.lax.dynamic_slice_in_dim(v, start, block_k, axis=1).astype(jnp.float32)
        kp = jax.lax.dynamic_slice_in_dim(k_pos, start, block_k, axis=0)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb)  # [B,Hkv,g,Sq,bk]
        mask = jnp.ones((Sq, block_k), bool)
        if causal:
            mask &= q_pos[:, None] >= kp[None, :]
        if window and window > 0:
            mask &= q_pos[:, None] - kp[None, :] < window
        mask &= kp[None, :] > -(10**8)  # padding
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vb)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Hkv, g, Sq, D), jnp.float32)
    m0 = jnp.full((B, Hkv, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), jnp.arange(nb) * block_k
    )
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


def banded_local_attention(q, k, v, *, window: int, q_pos=None):
    """Local (sliding-window) attention with FLOPs linear in S.

    Queries are chunked by ``window``; chunk i attends to key chunks {i-1, i}
    with exact masking, so compute is B*H*S*2W*D (vs S^2 for full attention).
    Requires Sq == Sk == S and S % window == 0 (callers pad).
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    W = window
    assert S % W == 0, (S, W)
    nc = S // W
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if q_pos is None:
        q_pos = jnp.arange(S)
    qc = (q.astype(jnp.float32) * scale).reshape(B, nc, W, Hkv, g, D)
    kc = k.astype(jnp.float32).reshape(B, nc, W, Hkv, D)
    vc = v.astype(jnp.float32).reshape(B, nc, W, Hkv, D)
    # previous chunk (chunk -1 is zeros, masked out by position)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kc], axis=2)  # [B,nc,2W,Hkv,D]
    v2 = jnp.concatenate([vprev, vc], axis=2)
    qp = q_pos.reshape(nc, W)
    kp_self = q_pos.reshape(nc, W)
    kp_prev = jnp.concatenate([jnp.full((1, W), -(10**9)), kp_self[:-1]], axis=0)
    kp = jnp.concatenate([kp_prev, kp_self], axis=1)  # [nc, 2W]
    s = jnp.einsum("bcqhgd,bckhd->bchgqk", qc, k2)
    mask = (qp[:, :, None] >= kp[:, None, :]) & (qp[:, :, None] - kp[:, None, :] < W)
    s = jnp.where(mask[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bchgqk,bckhd->bcqhgd", p, v2)
    return o.reshape(B, S, H, D).astype(q.dtype)


def chunk_attention(q, k_cache, v_cache, *, q_pos, k_pos, window: int = 0):
    """Multi-token append attention over a populated KV cache.

    q [B,k,H,D] (a chunk of k new tokens already written into the cache);
    caches [B,S,Hkv,D]; q_pos [B,k]; k_pos [B,S] (slot positions, -1 empty).
    Causality/window masking is positional, so ring-buffer caches work.
    The batched-replay fast path of MS2M (core/consumer.replay_chunked).
    """
    B, K, H, D = q.shape
    Hkv = k_cache.shape[2]
    g = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qg = (q.astype(jnp.float32) * scale).reshape(B, K, Hkv, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache.astype(jnp.float32))
    valid = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window and window > 0:
        valid &= q_pos[:, :, None] - k_pos[:, None, :] < window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, K, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, q_pos, k_pos):
    """Single-token attention over a (possibly seq-sharded) KV cache.

    q [B,1,H,D]; caches [B,S,Hkv,D]; q_pos [B] current position; k_pos [B,S]
    cache slot positions (-1 = empty).  Softmax reductions over the sharded S
    axis lower to flash-decode-style partial reductions + psum under SPMD.
    """
    B, _, H, D = q.shape
    Hkv = k_cache.shape[2]
    g = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    # contract in the cache's native dtype with fp32 MXU accumulation —
    # materializing an fp32 copy of the cache would triple decode HBM
    # traffic (EXPERIMENTS.md §Perf C3)
    qg = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32)
    valid = (k_pos >= 0) & (k_pos <= q_pos[:, None])  # [B,S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# routed experts at decode
# ---------------------------------------------------------------------------

def _expert_ffn(x, w_gate, w_up, w_down):
    """silu(x W_gate) * (x W_up), then W_down; x [..., D] against one
    expert's (or a leading axis of experts') matrices."""
    h = jax.nn.silu(jnp.einsum("...d,...df->...f", x, w_gate))
    h = h * jnp.einsum("...d,...df->...f", x, w_up)
    return jnp.einsum("...f,...fd->...d", h, w_down)


def naive_moe_decode(x, logits, w_gate, w_up, w_down, *, k, live):
    """Float32 ground truth for one decode token per lane: every expert
    runs on every lane and the dense top-``k`` gates (softmax over all
    experts, zero outside the ``k`` largest, renormalised) weight them; no
    capacity, idle lanes zero. x [B, D]; logits [B, E]; weights [E, ...]."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        probs = jax.nn.softmax(f32(logits), axis=-1)
        kth = jnp.sort(probs, axis=-1)[:, -k][:, None]
        gates = jnp.where(probs >= kth, probs, 0.0)
        gates = gates / gates.sum(-1, keepdims=True) * f32(live)[:, None]
        per_expert = _expert_ffn(f32(x)[:, None], f32(w_gate)[None],
                                 f32(w_up)[None], f32(w_down)[None])
        return jnp.einsum("be,bed->bd", gates, per_expert)


def moe_decode(x, gate_w, gate_ids, live, w_gate, w_up, w_down, layer=0):
    """The routed-expert kernel's jnp formulation: each (lane, k) pair
    through its own expert's weights, gathered; idle lanes zero. Weights
    ``[E, ...]``, or stacked ``[L, E, ...]`` read at ``layer``."""
    if w_gate.ndim == 4:
        pick = lambda w: jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
        w_gate, w_up, w_down = pick(w_gate), pick(w_up), pick(w_down)
    dt = w_gate.dtype
    y = _expert_ffn(x.astype(dt)[:, None], w_gate[gate_ids], w_up[gate_ids],
                    w_down[gate_ids])                            # [B, K, D]
    gates = gate_w.astype(jnp.float32) * live[:, None]
    return jnp.einsum("bk,bkd->bd", gates, y.astype(jnp.float32))


# ---------------------------------------------------------------------------
# RG-LRU (griffin / recurrentgemma) oracle
# ---------------------------------------------------------------------------

def naive_rglru(x, a_param, gate_a, gate_x, h0=None, *, c: float = 8.0):
    """Real-Gated Linear Recurrent Unit (arXiv:2402.19427 eq. 1-4).

    x, gate_a, gate_x: [B,S,W];  a_param: [W] (raw; a = sigmoid(a_param)).
      r_t = sigmoid(gate_a_t);  i_t = sigmoid(gate_x_t)
      a_t = a^(c*r_t)           (log-space: exp(c*r_t*log_sigmoid(a_param)))
      h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * (i_t * x_t)
    Returns (h_seq [B,S,W], h_last [B,W]).
    """
    B, S, W = x.shape
    log_a = jax.nn.log_sigmoid(a_param.astype(jnp.float32))  # [W]
    r = jax.nn.sigmoid(gate_a.astype(jnp.float32))
    i = jax.nn.sigmoid(gate_x.astype(jnp.float32))
    log_at = c * r * log_a[None, None, :]  # [B,S,W]
    a_t = jnp.exp(log_at)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 1e-12))
    gated = beta * (i * x.astype(jnp.float32))
    h = jnp.zeros((B, W), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, t):
        h = a_t[:, t] * h + gated[:, t]
        return h, h

    h_last, hs = jax.lax.scan(step, h, jnp.arange(S))
    return hs.transpose(1, 0, 2).astype(x.dtype), h_last


def blockwise_rglru(x, a_param, gate_a, gate_x, h0=None, *, c: float = 8.0,
                    block: int = 256):
    """Chunked associative formulation: within a chunk, prefix products of a_t
    give h_t = A_t*h_in + sum_j (A_t/A_j)*g_j computed as one einsum; chunks
    chain through a lax.scan.  Matches the Pallas kernel's grid structure."""
    B, S, W = x.shape
    assert S % block == 0 or S < block
    blk = min(block, S)
    pad = (-S) % blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        gate_a = jnp.pad(gate_a, ((0, 0), (0, pad), (0, 0)))
        gate_x = jnp.pad(gate_x, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nb = Sp // blk
    log_a = jax.nn.log_sigmoid(a_param.astype(jnp.float32))
    r = jax.nn.sigmoid(gate_a.astype(jnp.float32))
    i = jax.nn.sigmoid(gate_x.astype(jnp.float32))
    log_at = c * r * log_a[None, None, :]
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 1e-12)) * (
        i * x.astype(jnp.float32)
    )
    log_at = log_at.reshape(B, nb, blk, W)
    gated = gated.reshape(B, nb, blk, W)
    h = jnp.zeros((B, W), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def chunk(h, inputs):
        la, g = inputs  # [B,blk,W]
        cum = jnp.cumsum(la, axis=1)  # log prefix products A_t
        # h_t = exp(cum_t) * h + sum_{j<=t} exp(cum_t - cum_j) * g_j
        # stable: factor exp(cum_t) * sum_j exp(-cum_j) g_j can overflow;
        # use pairwise differences via triangular mask in log space.
        t_idx = jnp.arange(blk)
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # [B,t,j,W]
        tri = (t_idx[:, None] >= t_idx[None, :])[None, :, :, None]
        # a_t <= 1 so diff = cum_t - cum_j <= 0 for t >= j: exp is safe.
        w = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
        hs = jnp.exp(cum) * h[:, None, :] + jnp.einsum("btjw,bjw->btw", w, g)
        return hs[:, -1, :], hs

    h_last, hs = jax.lax.scan(chunk, h, (log_at.transpose(1, 0, 2, 3), gated.transpose(1, 0, 2, 3)))
    hs = hs.transpose(1, 0, 2, 3).reshape(B, Sp, W)[:, :S]
    return hs.astype(x.dtype), h_last


def rglru_decode_step(h, x, a_param, gate_a, gate_x, *, c: float = 8.0):
    """One-token RG-LRU update.  h [B,W]; x/gates [B,W]."""
    log_a = jax.nn.log_sigmoid(a_param.astype(jnp.float32))
    r = jax.nn.sigmoid(gate_a.astype(jnp.float32))
    i = jax.nn.sigmoid(gate_x.astype(jnp.float32))
    log_at = c * r * log_a[None, :]
    a_t = jnp.exp(log_at)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 1e-12))
    h_new = a_t * h.astype(jnp.float32) + beta * (i * x.astype(jnp.float32))
    return h_new


# ---------------------------------------------------------------------------
# mLSTM (xLSTM) oracle
# ---------------------------------------------------------------------------

def naive_mlstm(q, k, v, i_gate, f_gate, state=None):
    """Matrix-LSTM (arXiv:2405.04517 §2.3), stabilized recurrent form.

    q,k,v: [B,S,H,D]; i_gate,f_gate: [B,S,H] (pre-activations).
      C_t = f_t C_{t-1} + i_t v_t k_t^T ;  n_t = f_t n_{t-1} + i_t k_t
      h_t = C_t q_t / max(|n_t^T q_t|, 1)
    with the m_t log-stabilizer from the paper.  Returns (h [B,S,H,D], state).
    """
    B, S, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q = q.astype(jnp.float32) * scale
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))  # [B,S,H]
    logi = i_gate.astype(jnp.float32)
    if state is None:
        C0 = jnp.zeros((B, H, D, D), jnp.float32)
        n0 = jnp.zeros((B, H, D), jnp.float32)
        m0 = jnp.full((B, H), NEG_INF, jnp.float32)
    else:
        C0, n0, m0 = state

    def step(carry, t):
        C, n, m = carry
        m_new = jnp.maximum(logf[:, t] + m, logi[:, t])
        fe = jnp.exp(logf[:, t] + m - m_new)  # [B,H]
        ie = jnp.exp(logi[:, t] - m_new)
        C = fe[..., None, None] * C + ie[..., None, None] * (
            v[:, t][..., :, None] * k[:, t][..., None, :]
        )  # C[b,h,dv,dk]
        n = fe[..., None] * n + ie[..., None] * k[:, t]
        num = jnp.einsum("bhvk,bhk->bhv", C, q[:, t])
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", n, q[:, t]))
        den = jnp.maximum(den, jnp.exp(-m_new))  # paper's stabilized max(|n q|, exp(-m))
        h = num / den[..., None]
        return (C, n, m_new), h

    (C, n, m), hs = jax.lax.scan(step, (C0, n0, m0), jnp.arange(S))
    return hs.transpose(1, 0, 2, 3).astype(q.dtype), (C, n, m)


def mlstm_decode_step(state, q, k, v, i_gate, f_gate):
    """One-token mLSTM update. q/k/v [B,H,D]; gates [B,H]."""
    C, n, m = state
    D = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q = q.astype(jnp.float32) * scale
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    logi = i_gate.astype(jnp.float32)
    m_new = jnp.maximum(logf + m, logi)
    fe = jnp.exp(logf + m - m_new)
    ie = jnp.exp(logi - m_new)
    C = fe[..., None, None] * C + ie[..., None, None] * (v[..., :, None] * k[..., None, :])
    n = fe[..., None] * n + ie[..., None] * k
    num = jnp.einsum("bhvk,bhk->bhv", C, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, q)), jnp.exp(-m_new))
    return (C, n, m_new), (num / den[..., None])


def naive_slstm(x_i, x_f, x_z, x_o, r_i, r_f, r_z, r_o, state=None):
    """Scalar-LSTM with exponential gating + block-diagonal (per-head)
    recurrent mixing, as in arXiv:2405.04517 §2.2.

    x_* : [B,S,W] input pre-activations; r_* : [H, hb, hb] per-head
    recurrent weights applied to h_{t-1} (W = H*hb).  Returns (h_seq,
    state).  sLSTM is inherently sequential — no parallel form; per-head
    independence is what the Pallas kernel parallelizes over.
    """
    B, S, W = x_i.shape
    H, hb = r_i.shape[0], r_i.shape[1]
    assert H * hb == W, (H, hb, W)

    def rec(h, r):  # [B,W] x [H,hb,hb] -> [B,W]
        return jnp.einsum("bhi,hij->bhj", h.reshape(B, H, hb),
                          r.astype(jnp.float32)).reshape(B, W)

    if state is None:
        c0 = jnp.zeros((B, W), jnp.float32)
        n0 = jnp.ones((B, W), jnp.float32)
        h0 = jnp.zeros((B, W), jnp.float32)
        m0 = jnp.zeros((B, W), jnp.float32)
    else:
        c0, n0, h0, m0 = state

    def step(carry, t):
        c, n, h, m = carry
        zi = x_i[:, t].astype(jnp.float32) + rec(h, r_i)
        zf = x_f[:, t].astype(jnp.float32) + rec(h, r_f)
        zz = x_z[:, t].astype(jnp.float32) + rec(h, r_z)
        zo = x_o[:, t].astype(jnp.float32) + rec(h, r_o)
        m_new = jnp.maximum(zf + m, zi)
        ie = jnp.exp(zi - m_new)
        fe = jnp.exp(zf + m - m_new)
        c = fe * c + ie * jnp.tanh(zz)
        n = fe * n + ie
        h = jax.nn.sigmoid(zo) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    (c, n, h, m), hs = jax.lax.scan(step, (c0, n0, h0, m0), jnp.arange(S))
    return hs.transpose(1, 0, 2).astype(x_i.dtype), (c, n, h, m)
