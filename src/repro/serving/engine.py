"""Slot-based serving engine with continuous batching.

A fixed decode batch of ``num_slots`` sequences; requests admit into free
slots, their prompt folded one token per engine step (forced lanes of the
batched step, while the other lanes keep decoding), every engine step
decodes one token for all active slots, finished sequences free their
slot.  Steps are dispatched back to back: each lane's last sampled token
stays on the device and feeds the next step, and the sampled tokens reach
the host at a sync, at the end of every message and before the state is
read or replaced.  State = (slot KV caches, slot table) — one pytree,
which makes the *whole engine* an MS2M-migratable worker: its message log
is the admitted request stream, and replaying it from a checkpoint
reproduces the engine bit-exactly (tests/test_serving.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import transformer as T
from repro.models.config import ModelConfig


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int = 16


@dataclasses.dataclass
class Completion:
    request_id: int
    tokens: List[int]


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def _decode_all(params, cfg, cache, tokens, positions):
    """One step of every lane -> (next tokens [B], cache). For a model
    with routed experts the tokens come as row 0 of a [2, B] array whose
    row 1 holds the expert weight fetches each lane's experts made, so
    that both reach the host in the one copy of the step's output."""
    logits, cache, fetches = T.lm_decode_step_with_fetches(
        params, tokens, positions, cfg, cache)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    if fetches is not None:
        tok = jnp.stack([tok, fetches])
    return tok, cache


@functools.cache
def _owned_step(step):
    """The engine's jit of ``step`` (``_decode_all``, or a function that
    calls it), fed on the device: ``host`` is the step's one host input,
    int32 ``[3, B]`` of forced tokens, positions and a mask of the lanes
    fed their own last sampled token, which ``last`` ``[B]`` holds on the
    device. Returns ``step``'s output, the lanes' sampled tokens (the next
    ``last``) and the cache. An idle lane (position -1) keeps its
    ``last``: a slot whose prompt has just been folded waits idle while
    prompts admitted in its last step are folded, and then feeds the
    token its prompt sampled. The whole step is one program, named after
    ``_decode_all``, which takes the donated cache and hands back the
    next, so no array outside it ever holds a donated buffer."""
    def _decode_all_fed(params, cfg, cache, host, last):
        tokens = jnp.where(host[2] != 0, last, host[0])
        out, cache = step(params, cfg, cache, tokens[:, None],
                          host[1][:, None])
        tok = out if out.ndim == 1 else out[0]
        return out, jnp.where(host[1] >= 0, tok, last), cache

    return jax.jit(_decode_all_fed, static_argnames=("cfg",),
                   donate_argnames=("cache",))


def _nbytes(arrays) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(arrays))


class ServingEngine:
    """Continuous-batching engine over ``num_slots`` decode lanes.

    The engine owns its cache: every step donates it, so the step updates
    it in place. ``state_tree`` hands out a copy, and ``load_state`` takes
    a copy of the device arrays it is given; neither shares a buffer with
    the cache a later step consumes.

    No step waits for the one before it. Positions, budgets, completions
    and admissions need no token value, so the host knows them at
    dispatch; each step's output is queued on its way to the host, and
    ``sync`` appends the queued tokens to their requests' lists (a
    ``Completion`` holds the same list). Every public method that hands
    out or replaces the state syncs first, and ``process`` and ``step``
    return synced."""

    def __init__(self, cfg: ModelConfig, params, num_slots: int = 4,
                 max_seq: int = 512, name: str = "engine"):
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.name = name
        self.cache = T.init_cache(cfg, num_slots, max_seq)
        self.positions = np.zeros(num_slots, np.int64)  # next position
        self.active = np.zeros(num_slots, bool)
        self.claimed = np.zeros(num_slots, bool)  # mid-prefill guard
        self.request_of_slot: Dict[int, int] = {}
        self.budget = np.zeros(num_slots, np.int64)
        self.generated: Dict[int, List[int]] = {}
        # on the device: each lane's last sampled token, fed to its next step
        self._last = jnp.zeros(num_slots, jnp.int32)
        # steps not yet read: (output on its way to the host,
        # [(slot, token list its sampled token goes to)])
        self._pending: List[tuple] = []
        self.waiting: List[Request] = []
        self.completions: List[Completion] = []
        # MS2M bookkeeping
        self.last_msg_id = -1
        self.n_processed = 0
        self.skip_until = -1
        self._step_jit = functools.partial(_owned_step(_decode_all),
                                           self.params, self.cfg)

    # ------------------------------------------------------------------ admin
    def submit(self, req: Request):
        self.waiting.append(req)
        self._admit_waiting()

    def _admit_waiting(self):
        while self.waiting and not (self.active | self.claimed).all():
            slot = int(np.flatnonzero(~(self.active | self.claimed))[0])
            req = self.waiting.pop(0)
            self._prefill_slot(slot, req)

    def _prefill_slot(self, slot: int, req: Request):
        """Fold the prompt into the slot with forced decode steps (other
        active lanes keep generating during the admission — continuous
        batching).  The last prompt step's logits yield the first sampled
        token, exactly like a plain prefill+decode."""
        toks = req.prompt or [0]
        self.claimed[slot] = True
        generated: List[int] = []
        with obs.span("engine.admit", request_id=req.request_id):
            for t, tok in enumerate(toks):
                into = generated if t == len(toks) - 1 else None
                self._engine_step(forced={slot: (tok, t, into)})
        self.claimed[slot] = False
        self.positions[slot] = len(toks)
        self.active[slot] = True
        self.request_of_slot[slot] = req.request_id
        self.generated[req.request_id] = generated
        self.budget[slot] = req.max_new_tokens - 1
        if self.budget[slot] <= 0:
            self._complete(slot)

    # ------------------------------------------------------------------- step
    def _engine_step(self, forced: Optional[Dict[int, tuple]] = None):
        """Dispatch one batched decode step across all slots.

        ``forced`` maps slot -> (token, position, into): lanes being
        prefilled consume their prompt token at its position, and the lane
        given a list ``into`` samples its first token into it; other active
        lanes decode their last sampled token, held on the device; idle
        lanes take position -1, which writes their own lane's row as empty
        and routes them to no expert (harmless: a lane's rows are rewritten
        from position 0 on admit)."""
        forced = forced or {}
        # the span ends before admissions, whose prefill runs steps of its
        # own: steps never nest
        with obs.span("engine.step"):
            # forced tokens, positions, lanes fed their last sampled token
            host = np.zeros((3, self.num_slots), np.int32)
            host[1] = -1
            sampled = []
            for s in range(self.num_slots):
                if s in forced:
                    host[0, s], host[1, s], into = forced[s]
                    if into is not None:
                        sampled.append((s, into))
                elif self.active[s]:
                    host[1, s] = self.positions[s]
                    host[2, s] = 1
                    rid = self.request_of_slot[s]
                    sampled.append((s, self.generated[rid]))
            with obs.span("engine.step.dispatch"):
                out, self._last, self.cache = self._step_jit(
                    self.cache, jnp.asarray(host), self._last)
            out.copy_to_host_async()
            self._pending.append((out, sampled))
            for s in np.flatnonzero(host[2]):
                self.positions[s] += 1
                self.budget[s] -= 1
                if (self.budget[s] <= 0
                        or self.positions[s] >= self.max_seq - 1):
                    self._complete(int(s))
            obs.count("engine.steps")
            obs.count("engine.lanes", int((host[1] >= 0).sum()))
        self._admit_waiting()

    def sync(self) -> None:
        """Read the queued steps' sampled tokens on the host, in step
        order: each goes to its request's token list. Returns once the
        last step dispatched has run."""
        if not self._pending:
            return
        with obs.span("engine.sync"):
            for out, sampled in self._pending:
                out = np.asarray(out)
                if out.ndim == 2:
                    out, fetches = out
                    obs.count("moe.expert_loads", int(fetches.sum()))
                for s, into in sampled:
                    into.append(int(out[s]))
            self._pending = []
        obs.count("engine.syncs")

    def _complete(self, slot: int):
        rid = self.request_of_slot.pop(slot)
        self.completions.append(Completion(rid, self.generated.pop(rid)))
        self.active[slot] = False
        self.positions[slot] = 0

    def step(self, n: int = 1):
        for _ in range(n):
            if self.active.any():
                self._engine_step()
        self.sync()

    # ------------------------------------------------------- MS2M worker API
    def process(self, msg) -> None:
        """Message = one request admission + its full generation (the
        deterministic unit the MS2M log replays)."""
        p = msg.payload
        req = Request(p.get("request_id", msg.msg_id),
                      list(p.get("prompt", [p.get("token", 0)])),
                      int(p.get("max_new_tokens", 8)))
        self.submit(req)
        while req.request_id in self.generated or any(
                r.request_id == req.request_id for r in self.waiting):
            self._engine_step()
        self.sync()
        self.last_msg_id = msg.msg_id
        self.n_processed += 1

    def state_tree(self):
        """Full checkpointable state: a device copy of the KV caches, the
        slot table, *and* the admitted-request log (per-slot request id +
        generated-so-far tokens), so a mid-generation checkpoint restores
        in-flight requests instead of dropping them.  The copy outlives
        the steps that follow, which consume the engine's own cache.  The
        log is derived from the bookkeeping dicts at snapshot time — no
        hot-path cost.  A non-empty admission backlog has no array form,
        so checkpoints are only taken between admissions (the serving
        wrapper guarantees this by draining ``waiting`` before yielding
        control)."""
        self.sync()
        if self.waiting:
            raise RuntimeError(
                f"{self.name}: state_tree() with {len(self.waiting)} "
                "request(s) still waiting for admission — drain the "
                "waiting queue before checkpointing")
        request = np.full(self.num_slots, -1, np.int64)
        gen_len = np.zeros(self.num_slots, np.int64)
        gen = np.zeros((self.num_slots, self.max_seq), np.int32)
        last_token = np.zeros(self.num_slots, np.int64)
        for s, rid in self.request_of_slot.items():
            toks = self.generated[rid]
            request[s] = rid
            last_token[s] = toks[-1]
            gen_len[s] = len(toks)
            gen[s, : len(toks)] = toks
        cache = jax.tree.map(jnp.copy, self.cache)
        obs.count("engine.snapshot_bytes", _nbytes(cache))
        return {
            "cache": cache,
            "slots": {
                "positions": self.positions.copy(),
                "active": self.active.copy(),
                "budget": self.budget.copy(),
                "last_token": last_token,
                "request": request,
                "gen_len": gen_len,
                "gen": gen,
            },
            "scalars": {
                "last_msg_id": np.int64(self.last_msg_id),
                "n_processed": np.int64(self.n_processed),
            },
        }

    def load_state(self, tree):
        """Restore from ``tree``: host leaves are put on the device, device
        leaves copied, so the caller's tree stays valid after later
        (donated) steps."""
        self.sync()
        with obs.span("engine.load"):
            leaves = jax.tree.leaves(tree["cache"])
            obs.count("restore.h2d_bytes", sum(
                np.asarray(x).nbytes for x in leaves
                if not isinstance(x, jax.Array)))
            obs.count("engine.snapshot_bytes", _nbytes(
                [x for x in leaves if isinstance(x, jax.Array)]))
            self.cache = jax.tree.map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array)
                else jnp.asarray(x), tree["cache"])
        slots = tree["slots"]
        self.positions = np.asarray(slots["positions"]).copy()
        self.active = np.asarray(slots["active"]).copy()
        self.budget = np.asarray(slots["budget"]).copy()
        self._last = jnp.asarray(
            np.asarray(slots["last_token"]).astype(np.int32))
        self.last_msg_id = int(tree["scalars"]["last_msg_id"])
        self.n_processed = int(tree["scalars"]["n_processed"])
        self.request_of_slot = {}
        self.generated = {}
        self.waiting = []
        if "request" in slots:  # admitted-request log (older trees lack it)
            request = np.asarray(slots["request"])
            gen_len = np.asarray(slots["gen_len"])
            gen = np.asarray(slots["gen"])
            for s in np.flatnonzero(request >= 0):
                rid = int(request[s])
                self.request_of_slot[int(s)] = rid
                self.generated[rid] = [int(t)
                                       for t in gen[s, : int(gen_len[s])]]

    def state_equal(self, other, exact: bool = True) -> bool:
        self.sync()
        other.sync()
        if self.last_msg_id != other.last_msg_id:
            return False
        for a, b in zip(jax.tree.leaves(self.cache),
                        jax.tree.leaves(other.cache)):
            a, b = np.asarray(a), np.asarray(b)
            ok = (np.array_equal(a, b) if exact
                  else np.allclose(a, b, rtol=1e-5, atol=1e-5))
            if not ok:
                return False
        return bool(
            np.array_equal(self.positions, other.positions)
            and np.array_equal(self.active, other.active)
            and self.request_of_slot == other.request_of_slot)

    def slot_table(self) -> List[Dict[str, int]]:
        """Human-readable view of the in-flight slots (handoff telemetry)."""
        self.sync()
        return [{"slot": s, "request_id": rid,
                 "position": int(self.positions[s]),
                 "generated": len(self.generated[rid]),
                 "budget": int(self.budget[s])}
                for s, rid in sorted(self.request_of_slot.items())]
