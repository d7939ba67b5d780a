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
            x, out[f"b{i}"] = T._apply_block_decode(
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
