"""The program's spans and counters (``repro.obs``): a trace taken with
the profiler on holds every declared span under its plain name, results
are the same with it on and off, and the counters count what their
names say, checked against counts made from shapes and from outside the
program."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.broker.broker import Message
from repro.checkpoint.registry import Registry
from repro.models import transformer as T
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingEngine

CHUNK = 4096


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("smollm_360m")
    return cfg, T.init_lm(jax.random.PRNGKey(0), cfg)


def messages(ids):
    return [Message(i, {"request_id": i,
                        "prompt": [3 + i, 5, 7 + i][:2 + i % 2],
                        "max_new_tokens": 3 + i % 3}, 0.0) for i in ids]


def workload(cfg, params, root):
    """Serve, push a full image and a delta, pull it into a second engine
    and serve on: every span of ``obs.SPANS`` runs."""
    a = ServingEngine(cfg, params, num_slots=2, max_seq=32, name="a")
    for m in messages(range(3)):
        a.process(m)
    reg = Registry(str(root), chunk_bytes=CHUNK)
    full = reg.push_image({"state": a.state_tree()})
    for m in messages([3]):
        a.process(m)
    delta = reg.push_delta({"state": a.state_tree()}, full.image_id,
                           compression="xor_rle")
    trees, _ = reg.pull_image(delta.image_id)
    b = ServingEngine(cfg, params, num_slots=2, max_seq=32, name="b")
    b.load_state(trees["state"])
    for e in (a, b):
        for m in messages([4]):
            e.process(m)
    return a, b


def host_events(logdir):
    """``(name, start, end, stats)`` of every event on the host plane;
    the stats of ``engine.admit`` only."""
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats) if e.name == "engine.admit" else {})
                           for e in line.events)
    return out


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    cfg, params = model
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        engines = workload(cfg, params, tmp_path_factory.mktemp("reg"))
    return engines, host_events(logdir)


def test_results_are_the_same_with_the_profiler_on(model, traced, tmp_path):
    cfg, params = model
    plain = workload(cfg, params, tmp_path)
    for on, off in zip(traced[0], plain):
        assert ([(c.request_id, c.tokens) for c in on.completions]
                == [(c.request_id, c.tokens) for c in off.completions])
        a, b = on.state_tree(), off.state_tree()
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def test_every_declared_span_is_on_the_host_plane(traced):
    names = {e[0] for e in traced[1]}
    assert set(obs.SPANS) <= names
    admits = [e[3] for e in traced[1] if e[0] == "engine.admit"]
    assert sorted(s["request_id"] for s in admits) == [0, 1, 2, 3, 4, 4]


def test_engine_steps_never_nest(traced):
    steps = sorted((s, e) for n, s, e, _ in traced[1] if n == "engine.step")
    assert len(steps) > 10
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(steps, steps[1:]))
    # dispatch and wait lie inside a step
    for name in ("engine.step.dispatch", "engine.step.wait"):
        for _, s, e, _ in (ev for ev in traced[1] if ev[0] == name):
            assert any(s0 <= s and e <= e0 for s0, e0 in steps)


def test_emitted_spans_are_declared(model, tmp_path, monkeypatch):
    cfg, params = model
    seen = []
    span = obs.span

    def recorded(name, **args):
        seen.append(name)
        return span(name, **args)
    monkeypatch.setattr(obs, "span", recorded)
    workload(cfg, params, tmp_path)
    assert set(seen) == set(obs.SPANS)
    with pytest.raises(KeyError):
        obs.count("no.such.counter")


def test_jit_compiles_rise_on_a_new_shape_only():
    f = jax.jit(lambda x: x * 2 + 1)
    x5, x7 = jnp.ones(5), jnp.ones(7)
    c0 = obs.counters()["jit.compiles"]
    f(x5)
    c1 = obs.counters()["jit.compiles"]
    f(x5)
    c2 = obs.counters()["jit.compiles"]
    f(x7)
    c3 = obs.counters()["jit.compiles"]
    assert (c1 - c0, c2 - c1, c3 - c2) == (1, 0, 1)


def test_jit_cache_loads_count_persistent_cache_hits(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    def g(x):
        return jnp.sin(x) * 3 - 1

    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        x = jnp.ones(11)
        jax.jit(g)(x)                  # compiled and written
        c0 = obs.counters()
        jax.clear_caches()
        jax.jit(g)(x)                  # read back
        c1 = obs.counters()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert c1["jit.cache_loads"] - c0["jit.cache_loads"] == 1
    assert c1["jit.compiles"] - c0["jit.compiles"] == 1


def test_engine_counts_steps_and_lanes(model, monkeypatch):
    cfg, params = model
    calls = []
    step = engine_mod._decode_all

    def counted(*args):
        calls.append(1)
        return step(*args)
    monkeypatch.setattr(engine_mod, "_decode_all", counted)
    eng = ServingEngine(cfg, params, num_slots=2, max_seq=32)
    msgs = messages(range(5))
    c0 = obs.counters()
    for m in msgs:
        eng.process(m)
    c1 = obs.counters()
    assert c1["engine.steps"] - c0["engine.steps"] == len(calls) > 0
    # a lane is fed once per prompt token and once per token sampled after
    # the first (the last prompt token's pass samples the first)
    prompts = sum(len(m.payload["prompt"]) for m in msgs)
    sampled = sum(len(c.tokens) - 1 for c in eng.completions)
    assert c1["engine.lanes"] - c0["engine.lanes"] == prompts + sampled


K_SHAPE = (4, 1024)                       # 16384 bytes: 4 chunks
K, POS, S = 16384, 2400, 8                # nbytes of k, pos, s
FPS = 16 * (4 + 1 + 1)                    # one 16-byte fingerprint a chunk


def leaf_state(k):
    """A device leaf of 4 chunks, a host leaf and a host scalar of one
    chunk each."""
    return {"k": jnp.asarray(k), "pos": np.arange(300, dtype=np.int64),
            "s": np.int64(5)}


def xor_plane_bytes(nbytes):
    """The fused kernel's XOR words for one leaf: whole chunks, or one
    chunk padded to 128-word rows for a leaf smaller than a chunk."""
    words = -(-nbytes // 4)
    per = CHUNK // 4
    if words <= per:
        return -(-words // 128) * 128 * 4
    return -(-words // per) * CHUNK


def diff(a, b):
    return {n: b[n] - a[n] for n in a}


@pytest.mark.parametrize("case", ["full", "delta", "delta_dense", "restore"])
def test_byte_counters_match_the_leaf_shapes(case, model, tmp_path):
    rng = np.random.default_rng(0)
    k = rng.standard_normal(K_SHAPE).astype(np.float32)
    reg = Registry(str(tmp_path), chunk_bytes=CHUNK)
    c0 = obs.counters()
    full = reg.push_image({"state": leaf_state(k)})
    c1 = obs.counters()
    if case == "full":
        d = diff(c0, c1)
        assert d["push.h2d_bytes"] == POS + S    # host leaves fingerprinted
        assert d["push.d2h_bytes"] == K + FPS    # k serialized whole
        assert d["push.hashed_bytes"] == K + POS + S
        assert d["push.parent_bytes"] == 0
        return
    if case == "restore":
        cfg, params = model
        eng = ServingEngine(cfg, params, num_slots=2, max_seq=32)
        for m in messages(range(2)):
            eng.process(m)
        tree = eng.state_tree()
        image = reg.push_image({"state": tree})
        c2 = obs.counters()
        pulled, _ = reg.pull_image(image.image_id)
        ServingEngine(cfg, params, num_slots=2,
                      max_seq=32).load_state(pulled["state"])
        d = diff(c2, obs.counters())
        assert d["restore.h2d_bytes"] == sum(
            x.nbytes for x in jax.tree.leaves(tree["cache"])) > 0
        assert d["pull.read_bytes"] == image.total_bytes
        return
    if case == "delta":
        k = k.copy()
        k[0, :4] += 1.0                   # one stripe: encodes small
    else:
        k = rng.standard_normal(K_SHAPE).astype(np.float32)
    delta = reg.push_delta({"state": leaf_state(k)}, full.image_id,
                           compression="xor_rle")
    d = diff(c1, obs.counters())
    assert delta.fused_leaves == 3
    assert d["push.parent_bytes"] == K + POS + S
    # the parent of every leaf, and the host leaves themselves
    assert d["push.h2d_bytes"] == K + POS + S + POS + S
    xor = sum(xor_plane_bytes(n) for n in (K, POS, S))
    # a chunk that encodes no smaller than it is goes raw: the device
    # leaf is then serialized whole
    raw = K if case == "delta_dense" else 0
    assert d["push.d2h_bytes"] == xor + FPS + raw
