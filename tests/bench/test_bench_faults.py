"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program below the drivers, the harness's
look for a chip is skipped (the program's reduced widths, on the CPU),
and the rest of a run goes as on the chip, with a window of one phase so
that what is compared does not hang on the CPU's speed. The limits here
are set for these widths from CPU readings (``SMOKE_LIMITS``)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.rehearse import smoke_cell
from repro.checkpoint.registry import Registry
from repro.serving import engine

# set from the serve cells' seeds 1-12 of both configurations: sound runs
# read at most 0.0911 (served_logit_gap, a near tie) and 0.0067
# (kv_rel_gap), the float8 control at least 0.0600 as kv_rel_gap; a token
# altered where it is produced reads several logits, a lane left stale
# about 1
SMOKE_LIMITS = {"served_logit_gap": {"limit": 0.5},
                "kv_rel_gap": {"limit": 0.025}}


def run(workload, seed=3):
    cell = smoke_cell(workload, seed, 0.0, False)
    cell.limits = dict(cell.limits, limits=dict(cell.limits["limits"],
                                                **SMOKE_LIMITS))
    return harness.driver(cell).run(cell, time.perf_counter())


def token_altered(monkeypatch):
    """The decode step hands slot 0 another token than its argmax."""
    step = engine._decode_all

    def altered(params, cfg, cache, tokens, positions):
        tok, cache = step(params, cfg, cache, tokens, positions)
        return tok.at[0].set((tok[0] + cfg.vocab_size // 2)
                             % cfg.vocab_size), cache
    monkeypatch.setattr(engine, "_decode_all", altered)


def state_unchanged(monkeypatch):
    """The decode step returns the cache it was given."""
    step = engine._decode_all

    def frozen(params, cfg, cache, tokens, positions):
        tok, _ = step(params, cfg, cache, tokens, positions)
        return tok, cache
    monkeypatch.setattr(engine, "_decode_all", frozen)


def half_batch_left_out(monkeypatch):
    """The decode step leaves out the first half of its lanes, where the
    traffic's requests land (the engine fills the lowest free slot): those
    lanes keep their cache and are handed token 0."""
    step = engine._decode_all

    def half(params, cfg, cache, tokens, positions):
        tok, new = step(params, cfg, cache, tokens, positions)
        out = jnp.arange(tokens.shape[0]) < tokens.shape[0] // 2
        cache = jax.tree.map(
            lambda n, o: jnp.where(out.reshape((1, -1) + (1,) * (n.ndim - 2)),
                                   o, n), new, cache)
        return jnp.where(out, 0, tok), cache
    monkeypatch.setattr(engine, "_decode_all", half)


def restore_corrupted(monkeypatch):
    """The pulled image differs from the pushed one in one byte."""
    pull = Registry.pull_image

    def corrupted(self, image_id, have_chunks=None):
        trees, n = pull(self, image_id, have_chunks)
        k = trees["state"]["cache"]["b0"]["k"]
        flat = k.reshape(-1).view(np.uint16)
        flat[0] ^= 1
        return trees, n
    monkeypatch.setattr(Registry, "pull_image", corrupted)


@pytest.mark.parametrize("seed", [1, 5, 6])
@pytest.mark.parametrize("workload", ["smollm_360m.handoff",
                                      "granite_moe_1b_a400m.serve"])
def test_sound_run_is_correct(workload, seed):
    assert run(workload, seed)["correct"]


@pytest.mark.parametrize("fault, workload", [
    (token_altered, "smollm_360m.serve"),
    (token_altered, "granite_moe_1b_a400m.handoff"),
    (state_unchanged, "smollm_360m.serve"),
    (state_unchanged, "granite_moe_1b_a400m.handoff"),
    (half_batch_left_out, "smollm_360m.serve"),
    (half_batch_left_out, "granite_moe_1b_a400m.handoff"),
    (restore_corrupted, "smollm_360m.handoff"),
])
def test_fault_is_not_correct(fault, workload, monkeypatch):
    fault(monkeypatch)
    assert not run(workload)["correct"]


@pytest.mark.parametrize("workload", ["smollm_360m.serve",
                                      "granite_moe_1b_a400m.handoff"])
def test_control_is_not_correct(workload, monkeypatch):
    """The float8 control's first choice at each served position, and its
    own keys and values, judged as if the program had produced them."""
    gaps = harness.served_gaps

    def control(*a, **k):
        got = gaps(*a, control=True)
        return dict(got, served_logit_gap=got["control_logit_gap"],
                    kv_rel_gap=got["control_kv_rel_gap"])
    monkeypatch.setattr(harness, "served_gaps", control)
    assert not run(workload)["correct"]
