"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + no NaNs, plus prefill/decode equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as T
from repro.models.common import split_params

ARCHS = configs.list_archs(include_paper=True)


def _batch(cfg, B=2, S=16, key=1):
    tokens = jax.random.randint(jax.random.PRNGKey(key), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.full((B, cfg.encoder_seq, cfg.d_model), 0.01)
    if cfg.frontend == "image_patches":
        batch["patch_embeds"] = jnp.full((B, cfg.num_patches, cfg.d_model), 0.01)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_no_nans(arch):
    cfg = configs.get_smoke(arch)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, aux = T.lm_forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())
    loss, metrics = T.lm_loss(params, batch, cfg)
    assert np.isfinite(float(loss))
    assert 5.0 < float(loss) < 10.0  # ~ln(padded_vocab) at init


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    from repro.train import step as steplib
    cfg = configs.get_smoke(arch)
    tcfg = steplib.TrainStepConfig(remat="none", lr_peak=1e-3,
                                   warmup_steps=1, total_steps=4)
    params, _ = split_params(T.init_lm(jax.random.PRNGKey(0), cfg))
    from repro.optim import adamw
    opt = adamw.adamw_init(params, tcfg.opt)
    step_fn = jax.jit(steplib.build_train_step(cfg, tcfg))
    batch = _batch(cfg)
    l0 = None
    for s in range(3):
        params, opt, m = step_fn(params, opt, batch,
                                 jnp.asarray(s, jnp.int32))
        if l0 is None:
            l0 = float(m["loss"])
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < l0 + 0.5  # training on a fixed batch descends
    for leaf in jax.tree.leaves(params):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_equivalence(arch):
    """prefill(S) then decode(1) == forward(S+1) on the last position."""
    import dataclasses
    cfg = configs.get_smoke(arch)
    if cfg.num_experts:
        # dropless capacity: capacity-induced token drops differ between a
        # 26-token forward and a 1-token decode by design, not by bug
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    batch = _batch(cfg, B, S)
    cache = T.init_cache(cfg, B, 32)
    logits_p, cache = T.lm_prefill(params, batch, cfg, cache)
    fwd_logits, _ = T.lm_forward(params, batch, cfg)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(fwd_logits),
                               rtol=1e-4, atol=1e-4)
    tok = batch["tokens"][:, -1:]
    pos = jnp.full((B, 1), S, jnp.int32)
    logits_d, cache = T.lm_decode_step(params, tok, pos, cfg, cache)
    ext = dict(batch)
    ext["tokens"] = jnp.concatenate([batch["tokens"], tok], axis=1)
    ext.pop("labels")
    logits_f, _ = T.lm_forward(params, ext, cfg)
    np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                               np.asarray(logits_f[:, -1]),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("arch", ["paper_consumer", "gemma3_4b",
                                  "recurrentgemma_2b", "xlstm_350m",
                                  "granite_moe_1b_a400m"])
def test_append_matches_sequential_decode(arch):
    """lm_append (batched replay) == sequential lm_decode_step fold."""
    cfg = configs.get_smoke(arch)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, K = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, K), 0,
                              cfg.vocab_size)
    c_seq = T.init_cache(cfg, B, 32)
    c_app = T.init_cache(cfg, B, 32)
    if cfg.is_encoder_decoder:
        pytest.skip("append for enc-dec requires enc_out in cache")
    logits_seq = None
    for t in range(K):
        logits_seq, c_seq = T.lm_decode_step(
            params, toks[:, t:t + 1], jnp.full((B, 1), t, jnp.int32), cfg,
            c_seq)
    positions = jnp.broadcast_to(jnp.arange(K)[None], (B, K))
    logits_app, c_app = T.lm_append(params, toks, positions, cfg, c_app)
    np.testing.assert_allclose(np.asarray(logits_app[:, -1]),
                               np.asarray(logits_seq[:, 0]),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(c_seq), jax.tree.leaves(c_app)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-4)


def _per_layer_fold(params, tokens, positions, cfg, cache):
    """The decode step as a fold over layers, each layer's cache sliced out
    of the stack, updated alone and stacked back (the layer loop's xs/ys)."""
    from repro.models import common
    x = common.embed(params["embed"], tokens, cfg)
    layers = []
    for g in range(cfg.num_groups):
        group = jax.tree.map(lambda a: a[g], params["groups"])
        out = {}
        for i in range(len(cfg.pattern)):
            one = jax.tree.map(lambda a: a[g:g + 1], cache[f"b{i}"])
            x, out[f"b{i}"], _ = T._apply_block_decode(
                group[f"b{i}"], one, 0, x, positions, cfg, i, enc_out=None)
        layers.append(out)
    return (T._logits(params, x, cfg),
            jax.tree.map(lambda *a: jnp.concatenate(a), *layers))


DECODE_FOLD_CASES = {
    "attention": ("paper_consumer", {}),
    "local_ring": ("gemma3_4b", {}),          # 5 local (ring) + 1 global
    "int8_kv": ("paper_consumer", {"kv_cache_dtype": "int8"}),
    "rglru": ("recurrentgemma_2b", {}),
    "xlstm": ("xlstm_350m", {}),
}


@pytest.mark.parametrize("unroll", [False, True])
@pytest.mark.parametrize("case", sorted(DECODE_FOLD_CASES))
def test_in_place_decode_matches_per_layer_fold(case, unroll):
    """lm_decode_step writes each lane's row into the stacked cache in
    place: over several steps, with lanes at different positions and a
    cache short enough that the ring wraps, its logits and cache equal the
    per-layer fold's bit for bit."""
    import dataclasses
    arch, changes = DECODE_FOLD_CASES[case]
    cfg = dataclasses.replace(configs.get_smoke(arch), **changes)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, S, steps = 3, 16, 20
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, steps), 0,
                              cfg.vocab_size)
    step = jax.jit(lambda p, t, q, c: T.lm_decode_step(p, t, q, cfg, c,
                                                       unroll=unroll))
    fold = jax.jit(lambda p, t, q, c: _per_layer_fold(p, t, q, cfg, c))
    got = want = T.init_cache(cfg, B, S)
    for t in range(steps):
        pos = jnp.asarray([[t], [t + 3], [2 * t]], jnp.int32)
        lg, got = step(params, toks[:, t:t + 1], pos, got)
        lw, want = fold(params, toks[:, t:t + 1], pos, want)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seq_minor", [False, True])
@pytest.mark.parametrize("case", ["attention", "local_ring"])
def test_in_place_decode_in_the_kernel_matches_per_layer_fold(
        case, seq_minor, monkeypatch):
    """The same through the Pallas kernel (interpret mode), which reads the
    stack in place where the device keeps it seq-minor (a TPU, for heads
    narrower than 128 lanes) and a sliced layer otherwise."""
    import dataclasses
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ops, "_seq_minor", lambda *a: seq_minor)
    arch, changes = DECODE_FOLD_CASES[case]
    cfg = dataclasses.replace(configs.get_smoke(arch), **changes)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, S, steps = 3, 16, 6
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, steps), 0,
                              cfg.vocab_size)
    step = jax.jit(lambda p, t, q, c: T.lm_decode_step(p, t, q, cfg, c))
    fold = jax.jit(lambda p, t, q, c: _per_layer_fold(p, t, q, cfg, c))
    got = want = T.init_cache(cfg, B, S)
    for t in range(steps):
        pos = jnp.asarray([[t], [t + 3], [2 * t]], jnp.int32)
        lg, got = step(params, toks[:, t:t + 1], pos, got)
        lw, want = fold(params, toks[:, t:t + 1], pos, want)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full (published) configs carry the exact assigned hyperparams."""
    spec = {
        "codeqwen1_5_7b": (32, 4096, 32, 32, 13440, 92416),
        "gemma3_4b": (34, 2560, 8, 4, 10240, 262144),
        "chatglm3_6b": (28, 4096, 32, 2, 13696, 65024),
        "smollm_360m": (32, 960, 15, 5, 2560, 49152),
        "whisper_large_v3": (32, 1280, 20, 20, 5120, 51866),
        "llama4_maverick_400b_a17b": (48, 5120, 40, 8, 8192, 202048),
        "granite_moe_1b_a400m": (24, 1024, 16, 8, 512, 49155),
        "recurrentgemma_2b": (26, 2560, 10, 1, 7680, 256000),
        "qwen2_vl_72b": (80, 8192, 64, 8, 29568, 152064),
        "xlstm_350m": (24, 1024, 4, 4, 0, 50304),
    }
    if arch not in spec:
        pytest.skip("paper consumer has no external spec")
    cfg = configs.get_config(arch)
    L, d, H, kv, ff, V = spec[arch]
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.num_heads == H and cfg.num_kv_heads == kv
    assert cfg.d_ff == ff and cfg.vocab_size == V


def test_int8_kv_cache_decode_close():
    """Quantized KV serving stays close to the bf16 fold (per-head int8)."""
    import dataclasses
    base = configs.get_smoke("paper_consumer")
    q8 = dataclasses.replace(base, kv_cache_dtype="int8")
    params = T.init_lm(jax.random.PRNGKey(0), base)
    B, S = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, base.vocab_size)
    def run(cfg):
        cache = T.init_cache(cfg, B, 32)
        logits = None
        for t in range(S):
            logits, cache = T.lm_decode_step(
                params, toks[:, t:t+1], jnp.full((B, 1), t, jnp.int32),
                cfg, cache)
        return logits
    lf = run(base)
    lq = run(q8)
    # int8 quantization error is bounded; logits must stay close
    err = float(jnp.abs(lf - lq).max())
    assert err < 0.15, err


def test_moe_local_routing_matches_global():
    """The scatter-free local-routing MoE == global pool at dropless
    capacity (the §Perf A optimization preserves semantics)."""
    import dataclasses
    cfg = dataclasses.replace(configs.get_smoke("granite_moe_1b_a400m"),
                              capacity_factor=8.0)
    from repro.models import moe as moelib
    p = moelib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.1
    out_l, aux_l = moelib.moe_forward(
        p, x, dataclasses.replace(cfg, moe_routing="local"))
    out_g, aux_g = moelib.moe_forward(
        p, x, dataclasses.replace(cfg, moe_routing="global"))
    np.testing.assert_allclose(np.asarray(out_l, np.float32),
                               np.asarray(out_g, np.float32),
                               rtol=1e-5, atol=1e-6)
    assert abs(float(aux_l) - float(aux_g)) < 1e-6


def test_moe_expert_counts():
    l4 = configs.get_config("llama4_maverick_400b_a17b")
    assert l4.num_experts == 128 and l4.num_experts_per_tok == 1
    gr = configs.get_config("granite_moe_1b_a400m")
    assert gr.num_experts == 32 and gr.num_experts_per_tok == 8


def test_moe_routing_mass_conservation():
    """Tokens that fit capacity emerge weighted; dropped tokens pass zero."""
    from repro.models import moe as moelib
    cfg = configs.get_smoke("granite_moe_1b_a400m")
    p = moelib.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.1
    out, aux = moelib.moe_forward(p, x, cfg)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    assert not bool(jnp.isnan(out).any())


def test_routed_decode_matches_forward_and_capacity_dispatch(monkeypatch):
    """granite's smoke config through the cache, token by token: the decode
    step with the routed-expert kernel (interpret mode) gives lm_forward's
    logits at every position, and the capacity dispatch's run at S = 1 in
    its place; lane 2 stays idle (position -1) for the first steps, which
    changes no live lane. Float32 at smoke widths: 1e-4 leaves room for
    sums taken in other orders (the forward's blockwise attention, the
    kernel's per-pair expert sums), while one expert left out or one gate
    not renormalised moves logits by 1e-2 or more."""
    import dataclasses
    from repro.models import moe as moelib
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    # dropless capacity, so that the forward drops no token either
    cfg = dataclasses.replace(configs.get_smoke("granite_moe_1b_a400m"),
                              capacity_factor=8.0)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    B, S, late = 3, 10, 3
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                              cfg.vocab_size)
    fwd, _ = T.lm_forward(params, {"tokens": toks, "labels": toks}, cfg)

    def decode():
        step = jax.jit(lambda p, t, q, c: T.lm_decode_step(p, t, q, cfg, c))
        cache, out = T.init_cache(cfg, B, 16), []
        for t in range(S):
            pos = np.array([t, t, t - late])
            tok = np.asarray(toks)[np.arange(B), np.maximum(pos, 0)]
            pos = np.where(pos >= 0, pos, -1)
            lg, cache = step(params, jnp.asarray(tok[:, None], jnp.int32),
                             jnp.asarray(pos[:, None], jnp.int32), cache)
            out.append(np.asarray(lg[:, 0]))
        return np.stack(out, 1)    # [B, S, V]; lane 2 late by ``late``

    def capacity(params, x, cfg, live, experts=None, layer=0):
        out, _ = moelib._moe_forward_local(params, x, cfg)
        return out, jnp.zeros(x.shape[0], jnp.int32)

    routed = decode()
    monkeypatch.setattr(moelib, "moe_decode", capacity)
    dispatched = decode()
    fwd = np.asarray(fwd)
    for got in (routed, dispatched):
        np.testing.assert_allclose(got[:2], fwd[:2], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got[2, late:], fwd[2, :S - late],
                                   rtol=1e-4, atol=1e-4)


def test_decode_router_breaks_near_ties_in_float32():
    """The decode step's router reads the normed input unrounded, in
    float32: two experts whose logits differ by less than bfloat16 can tell
    apart (1.00293 and 1.00195 both round to 1.0) are ordered as float32
    orders them. Only the first four inputs reach the router: expert 2
    always wins the first of the two slots, and expert 1 beats expert 0
    for the second by 0.001. The same input rounded to
    bfloat16 first, as the capacity dispatch reads it, ties them and takes
    expert 0, so the case tells the two apart; the experts' outputs then
    differ by far more than the 2e-2 that bfloat16 expert products leave."""
    import dataclasses
    from repro.kernels import ref
    from repro.models import moe as moelib
    cfg = dataclasses.replace(configs.get_smoke("granite_moe_1b_a400m"),
                              dtype="bfloat16")
    D, E, F, K = cfg.d_model, cfg.num_experts, cfg.d_ff, cfg.num_experts_per_tok
    assert (E, K) == (4, 2)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    w = {name: (jax.random.normal(k, (E,) + shape) / np.sqrt(shape[0])
                ).astype(jnp.bfloat16)
         for k, (name, shape) in zip(ks, (("w_gate", (D, F)),
                                          ("w_up", (D, F)),
                                          ("w_down", (F, D))))}
    router = np.zeros((D, E), np.float32)
    router[0, 0] = router[1, 1] = 1.0
    router[2, 2] = 2.0
    router[3, 3] = -2.0
    x = np.random.default_rng(0).normal(size=(1, 1, D)).astype(np.float32)
    x[0, 0, :4] = [1 + 2 ** -9, 1 + 3 * 2 ** -10, 1.0, 1.0]
    params = dict(w, router=jnp.asarray(router, jnp.bfloat16))
    live = jnp.ones((1,), bool)

    out, _ = moelib.moe_decode(params, jnp.asarray(x), cfg, live)
    logits = x[:, 0] @ router
    want = ref.naive_moe_decode(x[:, 0], logits, w["w_gate"], w["w_up"],
                                w["w_down"], k=K, live=live)
    _, _, rounded_ids = moelib.top_k_gates(
        jnp.asarray(x[:, 0], jnp.bfloat16).astype(jnp.float32) @ router, K)
    assert sorted(np.asarray(rounded_ids)[0].tolist()) == [0, 2]
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)
    wrong = ref.naive_moe_decode(
        x[:, 0], np.where(np.arange(E) == 1, -9.0, logits), w["w_gate"],
        w["w_up"], w["w_down"], k=K, live=live)
    assert np.abs(np.asarray(want) - np.asarray(wrong)).max() > 0.2


def test_decode_step_carries_the_residual_in_float32():
    """In a bfloat16 model the decode step's residual stream is float32:
    a block takes the embedding's bfloat16 rows and returns float32, the
    routed experts' sum joins it unrounded, and the step's logits (about
    0.5 at most) stay within 0.02 of the same step run wholly in float32 on
    the same bfloat16-valued weights: each sublayer's input is still
    rounded to bfloat16 once (relative error 2**-9), which over the smoke
    config's two layers moves them by about 0.005."""
    import dataclasses
    from repro.models import common
    from repro.models import moe as moelib
    cfg = dataclasses.replace(configs.get_smoke("granite_moe_1b_a400m"),
                              dtype="bfloat16")
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          T.init_lm(jax.random.PRNGKey(0), cfg))
    B = 2
    toks = jnp.asarray([[3], [7]], jnp.int32)
    pos = jnp.asarray([[0], [0]], jnp.int32)
    x = common.embed(params["embed"], toks, cfg)
    assert x.dtype == jnp.bfloat16
    group = jax.tree.map(lambda a: a[0], params["groups"])
    cache = T.init_cache(cfg, B, 8)
    y, _, _ = T._apply_block_decode(group["b0"], cache["b0"], 0, x, pos, cfg,
                                    0, enc_out=None)
    assert y.dtype == jnp.float32
    h = common.rms_norm(y, group["b0"]["norm2"], cfg.norm_eps)
    out, _ = moelib.moe_decode(group["b0"]["moe"], h, cfg,
                               jnp.ones((B,), bool))
    assert out.dtype == jnp.float32

    lg, _ = T.lm_decode_step(params, toks, pos, cfg, cache)
    f32 = dataclasses.replace(cfg, dtype="float32")
    want, _ = T.lm_decode_step(
        jax.tree.map(lambda a: a.astype(jnp.float32), params), toks, pos,
        f32, T.init_cache(f32, B, 8))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want), atol=0.02)
