"""Per-kernel correctness: Pallas (interpret mode) and blockwise-jnp
formulations vs the naive oracles, swept over shapes/dtypes/masks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as pl_decode
from repro.kernels.flash_attention import flash_attention as pl_flash
from repro.kernels import moe_decode as md
from repro.kernels.rglru import rglru as pl_rglru

TOL = dict(rtol=2e-2, atol=2e-3)  # bf16-friendly
TOL32 = dict(rtol=1e-4, atol=1e-5)


def _qkv(key, B, S, H, Hkv, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    return q, k, v


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 4, 4, 64),    # MHA
    (2, 256, 8, 2, 64),    # GQA 4:1
    (1, 256, 4, 1, 128),   # MQA
    (2, 128, 6, 3, 32),    # odd ratios
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_kernel(B, S, H, Hkv, D, dtype, window):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, Hkv, D, dtype)
    want = ref.naive_attention(q, k, v, causal=True, window=window)
    got = pl_flash(q, k, v, causal=True, window=window,
                   block_q=64, block_k=64, interpret=True)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("block_k", [32, 128, 1024])
def test_blockwise_attention_matches_naive(block_k):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 192, 4, 2, 64, jnp.float32)
    want = ref.naive_attention(q, k, v, causal=True)
    got = ref.blockwise_attention(q, k, v, causal=True, block_k=block_k)
    np.testing.assert_allclose(got, want, **TOL32)


def test_banded_local_attention_matches_naive():
    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 256, 4, 2, 64, jnp.float32)
    want = ref.naive_attention(q, k, v, causal=True, window=64)
    got = ref.banded_local_attention(q, k, v, window=64)
    np.testing.assert_allclose(got, want, **TOL32)


@pytest.mark.parametrize("B,S,H,Hkv,D", [(2, 256, 8, 2, 64), (1, 128, 4, 4, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel(B, S, H, Hkv, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    qpos = jnp.array([S // 2, S - 1][:B])
    kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kpos = jnp.where(kpos <= qpos[:, None], kpos, -1)
    want = ref.decode_attention(q, kc, vc, q_pos=qpos, k_pos=kpos)
    got = pl_decode(q, kc, vc, qpos, kpos, block_k=64, interpret=True)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("seq_minor", [False, True])
@pytest.mark.parametrize("block_k", [64, 128, 256])
@pytest.mark.parametrize("layer", [0, 2, 3])
def test_decode_attention_reads_a_layer_of_the_stack(layer, block_k,
                                                     seq_minor):
    """On the decode step's stacked [L,B,S,Hkv,D] caches the kernel reads
    layer ``layer`` in place, in either orientation of its K/V blocks: the
    same as the reference on that slice, and the same bits as the kernel
    handed the slice itself."""
    L, B, S, H, Hkv, D = 4, 2, 256, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (L, B, S, Hkv, D), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (L, B, S, Hkv, D), jnp.bfloat16)
    qpos = jnp.array([S // 3, S - 1])
    kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    kpos = jnp.where(kpos <= qpos[:, None], kpos, -1)
    got = pl_decode(q, kc, vc, qpos, kpos, jnp.int32(layer),
                    seq_minor=seq_minor, block_k=block_k, interpret=True)
    want = ref.decode_attention(q, kc[layer], vc[layer], q_pos=qpos,
                                k_pos=kpos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL)
    sliced = pl_decode(q, kc[layer], vc[layer], qpos, kpos,
                       block_k=block_k, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sliced))


def _moe_case(case):
    """x [B,D], router logits [B,E], live [B], expert weights and k for a
    routing case; the weights are seeded normal draws over sqrt(fan_in)."""
    E, K, B, D, F = 32, 8, 3, 128, 64
    live = np.ones(B, bool)
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    logits = jax.random.normal(ks[0], (B, E))
    if case == "shared":        # every lane routes to the same 8 experts
        logits = jnp.broadcast_to(logits[:1], (B, E))
    elif case == "one_expert":  # top 1, every lane on expert 5
        K = 1
        logits = logits.at[:, 5].add(10.0)
    elif case == "idle":        # lanes 1 and 3 of 4 are empty slots
        B = 4
        logits = jax.random.normal(ks[0], (B, E))
        live = np.array([True, False, True, False])
    x = jax.random.normal(ks[1], (B, D))
    w = [jax.random.normal(k, s) / np.sqrt(s[1]) for k, s in
         zip(ks[2:], [(E, D, F), (E, D, F), (E, F, D)])]
    return x, logits, jnp.asarray(live), w, K


MOE_CASES = ["random", "shared", "one_expert", "idle"]


@pytest.mark.parametrize("case", MOE_CASES)
def test_moe_decode_kernel(case):
    """The routed-expert kernel (interpret mode) and its jnp formulation
    against dense float32 top-k gating over every expert. Float32
    throughout, so both agree to float32 rounding of sums over D and F
    (TOL32); an idle lane's output is exactly zero."""
    from repro.models.moe import top_k_gates
    x, logits, live, w, K = _moe_case(case)
    _, gate_w, gate_ids = top_k_gates(logits, K)
    want = ref.naive_moe_decode(x, logits, *w, k=K, live=live)
    got = md.moe_decode(x, gate_w, gate_ids, live, *w, interpret=True)
    lowered = ref.moe_decode(x, gate_w, gate_ids, live, *w)
    for out in (got, lowered):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   **TOL32)
    assert not np.asarray(got)[~np.asarray(live)].any()


@pytest.mark.parametrize("case", MOE_CASES)
def test_moe_decode_fetches_each_routed_expert_once(case):
    """In the kernel's pair order the expert (the weights' block index)
    changes only where a new routed expert starts: each expert a live lane
    routes to is fetched once, no other is, and idle lanes' pairs fetch
    nothing. ``fetches`` charges each fetch to one live lane."""
    from repro.models.moe import top_k_gates
    x, logits, live, w, K = _moe_case(case)
    _, _, gate_ids = top_k_gates(logits, K)
    _, expert, lane, on = (np.asarray(a) for a in md.route(gate_ids, live))
    routed = set(np.asarray(gate_ids)[np.asarray(live)].ravel().tolist())
    opened = [int(e) for i, e in enumerate(expert)
              if i == 0 or e != expert[i - 1]]
    assert sorted(opened) == sorted(routed)
    assert set(expert[on == 1].tolist()) == routed
    assert not on[lane == 1].any() if case == "idle" else on.all()
    per_lane = np.asarray(md.fetches(gate_ids, live))
    assert per_lane.sum() == len(routed)
    assert not per_lane[~np.asarray(live)].any()


@pytest.mark.parametrize("layer", [0, 2])
def test_moe_decode_reads_a_layer_of_the_stack(layer):
    """On the decode step's stacked [L,E,...] expert weights the kernel
    reads layer ``layer`` in place: the same bits as handed the slice."""
    from repro.models.moe import top_k_gates
    x, logits, live, w, K = _moe_case("random")
    stack = [jnp.stack([a * (1 + i) for i in range(3)]) for a in w]
    _, gate_w, gate_ids = top_k_gates(logits, K)
    got = md.moe_decode(x, gate_w, gate_ids, live, *stack, jnp.int32(layer),
                        interpret=True)
    sliced = md.moe_decode(x, gate_w, gate_ids, live,
                           *[a[layer] for a in stack], interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sliced))


@pytest.mark.parametrize("B,S,W", [(1, 64, 128), (2, 128, 256), (1, 96, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_kernel(B, S, W, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (B, S, W), dtype)
    ga = jax.random.normal(ks[1], (B, S, W), dtype)
    gx = jax.random.normal(ks[2], (B, S, W), dtype)
    a = jax.random.normal(ks[3], (W,), jnp.float32)
    want_seq, want_last = ref.naive_rglru(x, a, ga, gx)
    chunk = 32
    got_seq, got_last = pl_rglru(x, a, ga, gx, block_w=128, chunk=chunk,
                                 interpret=True)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got_seq, np.float32),
                               np.asarray(want_seq, np.float32), **tol)
    np.testing.assert_allclose(got_last, want_last, **TOL32)


def test_rglru_blockwise_matches_naive():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    B, S, W = 2, 160, 48
    x = jax.random.normal(ks[0], (B, S, W))
    ga = jax.random.normal(ks[1], (B, S, W))
    gx = jax.random.normal(ks[2], (B, S, W))
    a = jax.random.normal(ks[3], (W,))
    want_seq, want_last = ref.naive_rglru(x, a, ga, gx)
    got_seq, got_last = ref.blockwise_rglru(x, a, ga, gx, block=32)
    np.testing.assert_allclose(got_seq, want_seq, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(got_last, want_last, rtol=1e-3, atol=1e-4)


def test_rglru_state_carry():
    """Kernel with h0 continues exactly from a previous chunk."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    B, S, W = 1, 128, 128
    x = jax.random.normal(ks[0], (B, S, W))
    ga = jax.random.normal(ks[1], (B, S, W))
    gx = jax.random.normal(ks[2], (B, S, W))
    a = jax.random.normal(ks[3], (W,))
    full_seq, full_last = ref.naive_rglru(x, a, ga, gx)
    h_mid = ref.naive_rglru(x[:, :64], a, ga[:, :64], gx[:, :64])[1]
    got_seq, got_last = pl_rglru(x[:, 64:], a, ga[:, 64:], gx[:, 64:],
                                 h_mid, block_w=128, chunk=32, interpret=True)
    np.testing.assert_allclose(got_last, full_last, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,S,H,D", [(1, 64, 2, 32), (2, 128, 4, 64)])
@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_chunkwise_kernel(B, S, H, D, chunk, dtype):
    from repro.kernels.mlstm import mlstm as pl_mlstm
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, H, D), dtype)
    v = jax.random.normal(ks[2], (B, S, H, D), dtype)
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 2.0
    want, _ = ref.naive_mlstm(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), ig, fg)
    got = pl_mlstm(q, k, v, ig, fg, chunk=chunk, interpret=True)
    tol = dict(rtol=5e-2, atol=0.3) if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("B,S,H,hb", [(1, 32, 2, 16), (2, 64, 4, 32)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_slstm_kernel(B, S, H, hb, chunk):
    from repro.kernels.slstm import slstm as pl_slstm
    W = H * hb
    ks = jax.random.split(jax.random.PRNGKey(10), 8)
    xi, xf, xz, xo = (jax.random.normal(k, (B, S, W)) for k in ks[:4])
    ri, rf, rz, ro = (jax.random.normal(k, (H, hb, hb)) * 0.2
                      for k in ks[4:])
    want, _ = ref.naive_slstm(xi, xf, xz, xo, ri, rf, rz, ro)
    got = pl_slstm(xi, xf, xz, xo, ri, rf, rz, ro, chunk=chunk,
                   interpret=True)
    np.testing.assert_allclose(got, want, **TOL32)


def test_mlstm_scan_vs_decode_consistency():
    B, S, H, D = 2, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    ig = jax.random.normal(ks[3], (B, S, H))
    fg = jax.random.normal(ks[4], (B, S, H)) + 2.0
    hs, state = ref.naive_mlstm(q, k, v, ig, fg)
    st = (jnp.zeros((B, H, D, D)), jnp.zeros((B, H, D)),
          jnp.full((B, H), ref.NEG_INF))
    outs = []
    for t in range(S):
        st, h = ref.mlstm_decode_step(st, q[:, t], k[:, t], v[:, t],
                                      ig[:, t], fg[:, t])
        outs.append(h)
    np.testing.assert_allclose(jnp.stack(outs, 1), hs, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(st[0], state[0], rtol=1e-4, atol=1e-5)


def test_chunk_attention_matches_decode_fold():
    """lm_append's attention primitive == sequential decode attention."""
    B, S, H, Hkv, D = 1, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    S_cache = 128
    kc = jnp.zeros((B, S_cache, Hkv, D))
    vc = jnp.zeros((B, S_cache, Hkv, D))
    kpos = jnp.full((B, S_cache), -1, jnp.int32)
    knew = jax.random.normal(ks[0], (B, S, Hkv, D))
    vnew = jax.random.normal(ks[1], (B, S, Hkv, D))
    q = jax.random.normal(ks[2], (B, S, H, D))
    # populate cache with the chunk
    kc = kc.at[:, :S].set(knew)
    vc = vc.at[:, :S].set(vnew)
    kpos = kpos.at[:, :S].set(jnp.arange(S)[None])
    qpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    got = ref.chunk_attention(q, kc, vc, q_pos=qpos, k_pos=kpos)
    # reference: causal attention over the chunk
    want = ref.naive_attention(q, knew, vnew, causal=True)
    np.testing.assert_allclose(got, want, **TOL32)
