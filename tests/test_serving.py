"""Serving engine: continuous batching correctness + MS2M migratability."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.broker.broker import Message
from repro.models import transformer as T
from repro.serving import Request, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke("paper_consumer")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_single_request_matches_plain_decode(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    prompt = [5, 7, 11]
    eng.submit(Request(0, prompt, max_new_tokens=6))
    eng.step(16)
    assert len(eng.completions) == 1
    got = eng.completions[0].tokens
    # reference: plain greedy decode
    import jax.numpy as jnp
    cache = T.init_cache(cfg, 1, 64)
    logits = None
    for t, tok in enumerate(prompt):
        logits, cache = T.lm_decode_step(
            params, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([[t]], jnp.int32), cfg, cache)
    want = []
    tok = int(jnp.argmax(logits[0, -1]))
    pos = len(prompt)
    for _ in range(6):
        want.append(tok)
        logits, cache = T.lm_decode_step(
            params, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([[pos]], jnp.int32), cfg, cache)
        tok = int(jnp.argmax(logits[0, -1]))
        pos += 1
    assert got == want


def test_concurrent_requests_complete(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    for i in range(5):  # more requests than slots -> queueing
        eng.submit(Request(i, [3 + i, 9], max_new_tokens=4))
    for _ in range(60):
        eng.step()
        if len(eng.completions) == 5:
            break
    assert sorted(c.request_id for c in eng.completions) == list(range(5))
    assert all(len(c.tokens) == 4 for c in eng.completions)


def test_engine_is_ms2m_migratable(setup):
    """checkpoint -> replay message suffix == uninterrupted engine."""
    cfg, params = setup
    msgs = [Message(i, {"request_id": i, "prompt": [2 + i, 4],
                        "max_new_tokens": 3}, 0.0) for i in range(6)]
    a = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    for m in msgs:
        a.process(m)
    b = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    for m in msgs[:3]:
        b.process(m)
    snap = b.state_tree()
    c = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    c.load_state(snap)
    for m in msgs[3:]:
        c.process(m)
    assert c.state_equal(a), "engine replay diverged from full fold"


def _messages(ids):
    return [Message(i, {"request_id": i, "prompt": [2 + i, 4, 6],
                        "max_new_tokens": 4}, 0.0) for i in ids]


def _host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree["cache"])]


@pytest.mark.parametrize("source", ["state_tree", "load_state"])
def test_handed_out_cache_outlives_donated_steps(setup, source):
    """The engine donates its cache to every step. A ``state_tree``
    snapshot, and a device tree handed to ``load_state``, share no buffer
    with it: each still holds what it held after more steps, and a second
    engine loaded from it replays to the same state as the first."""
    cfg, params = setup
    msgs = _messages(range(6))
    a = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    for m in msgs[:3]:
        a.process(m)
    tree = a.state_tree()
    before = _host(tree)
    if source == "load_state":
        a.load_state(tree)          # the engine steps on from a copy
    engine_leaves = jax.tree.leaves(a.cache)
    for m in msgs[3:]:
        a.process(m)
    assert all(x.is_deleted() for x in engine_leaves)   # donated
    assert not any(x.is_deleted() for x in jax.tree.leaves(tree["cache"]))
    for x, y in zip(before, _host(tree)):
        np.testing.assert_array_equal(x, y)
    b = ServingEngine(cfg, params, num_slots=2, max_seq=64)
    b.load_state(tree)
    for m in msgs[3:]:
        b.process(m)
    assert b.state_equal(a)
