"""The ``handoff`` driver: the traffic through a replica that is handed off
to a second replica on the same chip, over and over, through the
program's checkpoint registry.

One handoff follows the order of iterative pre-copy with a fixed round
count (convergence and cut-off policy belong to the simulator and are not
measured here):

1. ``state_tree`` and ``push_image``: round 0, the full state;
2. ``delta_rounds`` times (the mix's ``handoff`` block): the source
   serves one group of messages, then ``state_tree`` and ``push_delta``
   against the previous round's image;
3. the source serves one more group: the dual-serving window, whose
   messages are logged;
4. pause: the target pulls the newest image, loads it (resident on the
   device), and replays the logged messages; the pulled state must equal
   the pushed one bit for bit, and the target must then equal the source
   (``state_equal(exact=True)``). The images are deleted and collected,
   and the target serves on as the next source.
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile
from typing import Dict, List

import numpy as np

from bench import harness as H
from bench.drivers.serve import serve_group
from bench.traffic import Traffic

SPANS = ("engine.process", "precopy", "checkpoint", "registry.push",
         "downtime", "registry.pull", "load_state", "replay",
         H.CHECK_SPAN, "registry.gc")


def _trees_equal(pushed, pulled) -> bool:
    """The pulled tree against the pushed one: device leaves compared on
    the device (the pulled cache is already resident there), the rest on
    the host."""
    import jax
    import jax.numpy as jnp

    a, ta = jax.tree.flatten(pushed)
    b, tb = jax.tree.flatten(pulled)
    if ta != tb:
        return False
    for x, y in zip(a, b):
        if isinstance(x, jax.Array):
            if x.shape != y.shape or x.dtype != y.dtype or not bool(
                    jnp.array_equal(x, y)):
                return False
        elif not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return True


def precopy_round(rec, reg, rep, parent, compression):
    """Checkpoint the source and push it; blocked until the image is
    stored. Returns the push report and the pushed tree."""
    with rec.span("precopy"):
        with rec.span("checkpoint"):
            tree = rep.worker.state_tree()
        with rec.span("registry.push"):
            if parent is None:
                r = reg.push_image({"state": tree})
            else:
                r = reg.push_delta({"state": tree}, parent.image_id,
                                   compression=compression)
    rec.add(pushes=1, push_state_bytes=r.total_bytes,
            push_written_bytes=r.written_bytes)
    return r, tree


def handoff(rec, reg, src, dst, delta_msgs: List[List], logged: List,
            traffic, before: Dict[int, int], served: Dict, compression: str):
    """One handoff from ``src`` to ``dst``, one delta round for each group
    of ``delta_msgs``; returns ``(ok, before)``, with ``before`` the new
    source's snapshot."""
    import jax

    rec.add(handoffs=1)
    images = [precopy_round(rec, reg, src, None, compression)[0]]
    for msgs in delta_msgs:
        before = serve_group(rec, src, msgs, traffic, before)
        delta, pushed = precopy_round(rec, reg, src, images[-1], compression)
        images.append(delta)
    before = serve_group(rec, src, logged, traffic, before)
    src.drain(served)
    with rec.span("downtime"):
        with rec.span("registry.pull"):
            trees, _ = reg.pull_image(images[-1].image_id)
        with rec.span("load_state"):
            dst.worker.load_state(trees["state"])
            jax.block_until_ready(dst.engine.cache)
    with rec.span(H.CHECK_SPAN):
        state = dst.worker.state_tree()
        round_trip = _trees_equal(pushed, state)
    del pushed, trees, state
    dst.skip()
    replay0 = H.snapshot(dst.engine)
    with rec.span("downtime"):
        with rec.span("replay"):
            for m in logged:
                dst.worker.process(m)
            jax.block_until_ready(dst.engine.cache)
    dst.skip()
    after = H.snapshot(dst.engine)
    _, n, ctx = H.passes(replay0, after, traffic.prompt_len)
    rec.add(all_n=n, all_ctx=ctx, replay_msgs=len(logged))
    with rec.span(H.CHECK_SPAN):
        same = dst.worker.state_equal(src.worker, exact=True)
    with rec.span("registry.gc"):
        for image in reversed(images):
            reg.delete_image(image.image_id)
        reg.gc()
    rec.add(round_trip_mismatches=int(not round_trip),
            state_mismatches=int(not same))
    return round_trip and same, after


def run(cell: H.Cell, t_start: float) -> Dict:
    from repro.checkpoint.registry import Registry
    from repro.serving.handoff import slot_aligned_chunk_bytes

    dev = H.devices(cell)
    cfg = H.program_config(cell)
    model = H.model_block(cell, cfg)
    params = H.make_weights(cell, cfg, model)
    traffic = Traffic(cell.mix, model["vocab_size"], cell.seed)
    H.check_lengths(cell, traffic)
    compression = cell.mix["handoff"]["compression"]
    rounds = int(cell.mix["handoff"]["delta_rounds"])
    if rounds < 1:
        raise ValueError(f"{cell.name}: a handoff needs a delta round, "
                         f"not {rounds}")
    rec = H.Recorder()
    src = H.Replica(cell, cfg, params, "a")
    dst = H.Replica(cell, cfg, params, "b")
    served: Dict = {}
    root = tempfile.mkdtemp(prefix="bench-registry-")
    try:
        reg = Registry(root, chunk_bytes=slot_aligned_chunk_bytes(src.worker))
        before = serve_group(rec, src, traffic.warmup(), traffic, {})
        # every program of a handoff compiles here: a handoff with no
        # messages between its rounds
        ok, before = handoff(rec, reg, src, dst, [[]] * rounds, [],
                             traffic, before, served, compression)
        src, dst = dst, src
        warm = {k: rec.counters.get(k, 0)
                for k in ("round_trip_mismatches", "state_mismatches")}
        rec.counters.clear()
        rec.spans.clear()
        failed = int(not ok)
        prof = H.Profile(rec, SPANS) if cell.trace else None
        # handoff h logs group h and runs its delta rounds on groups spaced
        # evenly around the cycle, so that each role sees every group
        step = traffic.n_groups // (rounds + 1)
        w0 = rec.clock()
        setup_s = w0 - t_start
        h = 0
        while True:
            deltas = [traffic.group(h + (j + 1) * step) for j in range(rounds)]
            with (prof.window() if (prof and h == 0)
                  else contextlib.nullcontext()):
                ok, before = handoff(rec, reg, src, dst, deltas,
                                     traffic.group(h), traffic, before,
                                     served, compression)
            failed += int(not ok)
            src, dst = dst, src
            h += 1
            if rec.clock() - w0 >= cell.seconds:
                break
        w1 = rec.clock()
        src.drain(served)
        mem = H.memory_peak_bytes()
        lanes = H.capture_lanes(cell, src, traffic)
        del src, dst, params
    finally:
        shutil.rmtree(root, ignore_errors=True)
    c = rec.counters
    return H.report(
        cell, dev, model, rec, prof, (w0, w1), setup_s, mem, served,
        traffic, lanes,
        attempted=int(c["requests"] + c["handoffs"]) + 1, failed=failed,
        numbers={k: v + c[k] for k, v in warm.items()},
        end_to_end={
            "precopy_round_ms": 1e3 * np.mean(rec.durations("precopy")),
            "downtime_ms": 1e3 * sum(rec.durations("downtime"))
            / c["handoffs"]})
