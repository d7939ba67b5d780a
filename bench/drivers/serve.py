"""The ``serve`` driver: the traffic through one replica, with no
checkpoint, push or restore. It is the control of the handoff cells:
registry and codec changes must read no change here."""
from __future__ import annotations

import contextlib
from typing import Dict

from bench import harness as H
from bench.traffic import Traffic

SPANS = ("engine.process",)


def serve_group(rec: H.Recorder, rep: H.Replica, msgs, traffic: Traffic,
                before: Dict[int, int]) -> Dict[int, int]:
    """Admit ``msgs`` one by one; count what the replica sampled."""
    for m in msgs:
        with rec.span("engine.process"):
            rep.worker.process(m)
    after = H.snapshot(rep.engine)
    tokens, n, ctx = H.passes(before, after, traffic.prompt_len)
    rec.add(tokens=tokens, requests=len(msgs), useful_n=n, useful_ctx=ctx,
            all_n=n, all_ctx=ctx)
    return after


def run(cell: H.Cell, t_start: float) -> Dict:
    dev = H.devices(cell)
    cfg = H.program_config(cell)
    model = H.model_block(cell, cfg)
    params = H.make_weights(cell, cfg, model)
    traffic = Traffic(cell.mix, model["vocab_size"], cell.seed)
    H.check_lengths(cell, traffic)
    rec = H.Recorder()
    rep = H.Replica(cell, cfg, params, "replica")
    served: Dict = {}

    before = serve_group(rec, rep, traffic.warmup(), traffic, {})
    rec.counters.clear()
    rec.spans.clear()
    prof = H.Profile(rec, SPANS) if cell.trace else None
    groups = traffic.groups()
    w0 = rec.clock()
    setup_s = w0 - t_start
    first = True
    while True:
        with prof.window() if (prof and first) else contextlib.nullcontext():
            before = serve_group(rec, rep, next(groups), traffic, before)
        first = False
        if rec.clock() - w0 >= cell.seconds:
            break
    w1 = rec.clock()
    rep.drain(served)
    mem = H.memory_peak_bytes()
    lanes = H.capture_lanes(cell, rep, traffic)
    del rep, params
    return H.report(cell, dev, model, rec, prof, (w0, w1), setup_s, mem,
                    served, traffic, lanes,
                    attempted=int(rec.counters["requests"]), failed=0,
                    numbers={})
