"""What every driver shares: the cell's files, the model and its weights,
the replicas, host spans, counters, the profiler, the correctness check
against the plain reference, and the result line.

A cell is named in ``BENCHMARK.json``; its configuration, traffic mix and
correctness limits are files found by name under ``bench/``. Nothing here
knows a particular cell.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WINDOW_SPAN = "bench.window"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH)[:-3].replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the manifest, with everything its files say."""
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    seed: int
    seconds: float
    trace: bool
    smoke: bool = False  # the program's reduced config, for runs on a CPU

    @property
    def deployment(self) -> Dict:
        return self.config["deployment"]


def load_cell(workload: str, seed: int, seconds: float, trace: bool,
              manifest: Optional[Dict] = None) -> Cell:
    manifest = manifest or _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    per_layer = [m for m in manifest["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in manifest["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return Cell(workload, int(w["chips"]),
                _json(os.path.join(BENCH, "configs", w["config"] + ".json")),
                _json(os.path.join(BENCH, "mixes", w["traffic"] + ".json")),
                _json(os.path.join(BENCH, "limits", workload + ".json")),
                end_to_end, per_layer, seed, seconds, trace)


def driver(cell: Cell):
    return _module(os.path.join(BENCH, "drivers", cell.mix["driver"] + ".py"))


# ---------------------------------------------------------------- devices

def devices(cell: Cell) -> Dict:
    """The device the run uses, as JAX reports it; raises ``NoDevice``
    unless JAX finds the accelerator and the chips the cell asks for."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if not cell.smoke and (d.platform == "cpu" or len(devs) < cell.chips):
        raise NoDevice(f"cell {cell.name} needs {cell.chips} accelerator "
                       f"chip(s); JAX found {len(devs)} {d.platform} "
                       f"device(s) ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": cell.chips}


def peaks(kind: str) -> Dict:
    table = _json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak_bytes() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ model

def reference_model(cfg) -> Dict:
    """The ``model`` block (published key names, values as run) of a
    program config; the reduced configs of CPU runs have no file."""
    m = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
         "num_attention_heads": cfg.num_heads,
         "num_key_value_heads": cfg.num_kv_heads,
         "head_dim": cfg.resolved_head_dim, "intermediate_size": cfg.d_ff,
         "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
         "rms_norm_eps": cfg.norm_eps,
         "embedding_multiplier": math.sqrt(cfg.d_model)}
    if cfg.num_experts:
        m["num_local_experts"] = cfg.num_experts
        m["num_experts_per_tok"] = cfg.num_experts_per_tok
    return m


def program_config(cell: Cell):
    """The program's ModelConfig for the cell, checked against the file:
    every number the file states as run must be what the program runs."""
    import dataclasses as dc

    from repro import configs

    name = cell.config["repo_config"]
    dtype = cell.deployment["dtype"]
    if cell.smoke:
        return dc.replace(configs.get_smoke(name), dtype=dtype)
    cfg = configs.get_config(name)
    want = dict(cell.config["model"], **cell.config["as_run"])
    got = reference_model(cfg)
    bad = {k: (want.get(k), v) for k, v in got.items()
           if not math.isclose(float(want.get(k, math.nan)), float(v),
                               rel_tol=1e-6)}
    if bad or cfg.dtype != dtype:
        raise ValueError(f"{name}: the program runs {bad} (file, program); "
                         f"dtype {cfg.dtype} vs {dtype}")
    return cfg


def model_block(cell: Cell, cfg) -> Dict:
    if cell.smoke:
        return reference_model(cfg)
    return dict(cell.config["model"], **cell.config["as_run"])


def make_weights(cell: Cell, cfg, model: Dict):
    """The seed's weights, checked against the tree the program builds."""
    import functools

    import jax

    from bench import weights
    from repro.models import transformer as T
    from repro.models.common import split_params

    params = weights.make(model, cell.seed, cell.deployment["dtype"])
    shapes, _ = split_params(jax.eval_shape(
        functools.partial(T.init_lm, cfg=cfg), jax.random.key(0)))
    want = jax.tree.map(lambda a: a.shape, shapes)
    got = jax.tree.map(lambda a: a.shape, params)
    if want != got:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} vs {want}")
    return jax.block_until_ready(params)


# --------------------------------------------------------------- replicas

def snapshot(engine) -> Dict[int, int]:
    """Tokens generated so far, by request id, finished or in flight."""
    out = {c.request_id: len(c.tokens) for c in engine.completions}
    out.update({rid: len(t) for rid, t in engine.generated.items()})
    return out


def passes(before: Dict[int, int], after: Dict[int, int],
           prompt_len: Dict[int, int]) -> Tuple[int, int, int]:
    """``(tokens sampled, passes, summed context)`` between two snapshots.
    A request new in ``after`` ran its whole prompt (the last prompt pass
    samples its first token); its g-th later token came from the pass at
    position ``P + g - 2``."""
    sampled = n = ctx = 0
    for rid, g1 in after.items():
        g0 = before.get(rid)
        P = prompt_len[rid]
        sampled += g1 - (g0 or 0)
        if g0 is None:
            n += P
            ctx += P * (P + 1) // 2
            g0 = 1
        a, b = max(g0, 1) + 1, g1
        if b >= a:
            n += b - a + 1
            ctx += (b - a + 1) * (2 * P + a + b - 2) // 2
    return sampled, n, ctx


class Replica:
    """One serving replica: the program's engine under its MS2M worker."""

    def __init__(self, cell: Cell, cfg, params, name: str):
        from repro.serving.engine import ServingEngine
        from repro.serving.handoff import ServingWorker

        dep = cell.deployment
        self.engine = ServingEngine(cfg, params, num_slots=dep["slots"],
                                    max_seq=dep["max_seq"], name=name)
        self.worker = ServingWorker(self.engine,
                                    decode_rounds=dep["decode_rounds"])
        self.delivered = 0  # completions already handed to users

    def drain(self, served: Dict[int, List[int]]) -> None:
        """Hand this replica's new completions to users (first wins)."""
        for c in self.engine.completions[self.delivered:]:
            served.setdefault(c.request_id, list(c.tokens))
        self.delivered = len(self.engine.completions)

    def skip(self) -> None:
        """Completions so far are replays: never delivered."""
        self.delivered = len(self.engine.completions)


# ------------------------------------------------------------------- runs

class Recorder:
    """Host spans (also written into the profiler's trace) and counters."""

    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self.counters: Dict[str, float] = {}
        self.clock = time.perf_counter

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = self.clock()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, self.clock()))

    def add(self, **kw) -> None:
        for k, v in kw.items():
            self.counters[k] = self.counters.get(k, 0) + v

    def durations(self, name: str, lo: float = -math.inf,
                  hi: float = math.inf) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and t0 >= lo and t1 <= hi]


class Profile:
    """The profiler over part of the window, into a temporary directory;
    ``reduce()`` gives the trace and removes the directory."""

    def __init__(self, rec: Recorder, span_names):
        self.rec = rec
        self.span_names = span_names
        self.dir = None
        self.counters0: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}

    @contextlib.contextmanager
    def window(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.counters0 = dict(self.rec.counters)
        jax.profiler.start_trace(self.dir)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        self.counters = {k: v - self.counters0.get(k, 0)
                         for k, v in self.rec.counters.items()}

    def reduce(self):
        from bench import trace

        try:
            return trace.load(trace.find_xplane(self.dir), self.span_names,
                              WINDOW_SPAN)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Run:
    """What a per-layer metric reads."""
    cell: Cell
    model: Dict
    peak: Dict
    trace: object                # bench.trace.Trace of the traced part
    counters: Dict[str, float]   # counted over the traced part
    rec: Recorder                # spans and counters of the whole window
    window: Tuple[float, float]  # the whole window, host clock


def per_layer(run: Run) -> Dict[str, Dict]:
    """Each per-layer metric of the cell, read by its own file. A reader
    that finds nothing to read returns None and its metric is left out;
    on the chip that is an error, since the manifest lists for each metric
    the cells in which it has something to read (on the CPU there is no
    device plane, and only host-clock readers find something)."""
    out, missing = {}, []
    for m in run.cell.per_layer:
        v = _module(os.path.join(BENCH, "metrics", m["name"] + ".py")).read(run)
        if v is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if missing and not run.cell.smoke:
        raise RuntimeError(f"{run.cell.name}: nothing to read for "
                           f"{', '.join(missing)} in the traced window")
    return out


# ------------------------------------------------------------ correctness

# in-flight lanes whose cache is compared, at most
MAX_LANES = 4


def capture_lanes(cell: Cell, rep: Replica, traffic) -> Dict:
    """The keys and values the replica's cache holds for up to
    ``MAX_LANES`` requests in flight, over as many positions as the
    traffic's longest request can fill (``lane_length``), copied to the
    host before the program's state is freed, with the tokens they were
    computed from (the prompt, then every sampled token but the last,
    which is not yet fed back)."""
    length = min(lane_length(traffic_shape(traffic)),
                 cell.deployment["max_seq"])
    eng = rep.engine
    if len(eng.cache) != 1:
        raise ValueError("lane capture reads one stacked attention cache")
    (blk,) = eng.cache.values()
    lanes = sorted(eng.request_of_slot.items())[:MAX_LANES]
    tokens = np.zeros((MAX_LANES, length), np.int32)
    lengths = np.zeros(MAX_LANES, np.int64)
    shape = (blk["k"].shape[0], MAX_LANES, length) + blk["k"].shape[3:]
    keys = np.zeros(shape, np.float32)
    values = np.zeros(shape, np.float32)
    for i, (slot, rid) in enumerate(lanes):
        seq = traffic.prompt_of[rid] + eng.generated[rid][:-1]
        if len(seq) != eng.positions[slot]:
            raise ValueError(f"slot {slot}: {len(seq)} tokens for position "
                             f"{eng.positions[slot]}")
        tokens[i, :len(seq)] = seq
        lengths[i] = len(seq)
        keys[:, i] = np.asarray(blk["k"][:, slot, :length], np.float32)
        values[:, i] = np.asarray(blk["v"][:, slot, :length], np.float32)
    return {"tokens": tokens, "lengths": lengths, "keys": keys,
            "values": values}


def served_gaps(cell: Cell, model: Dict, served: Dict[int, List[int]],
                prompt_of: Dict[int, List[int]], shape: Tuple[int, int],
                lanes: Dict, control: bool = False) -> Dict[str, float]:
    """What the plain reference says of the window's output:

    - ``served_logit_gap``: the sample of finished requests, drawn from the
      seed with the longest among them, run through the reference with
      their served tokens; the widest gap by which a served token's logit
      lies below the reference's best. ``shape`` is the traffic's longest
      request and longest answer, so that every seed's sample runs through
      the same compiled reference.
    - ``kv_rel_gap``: the keys and values cached for the requests in
      flight (``capture_lanes``) against the reference's, the median over
      each lane's positions and the largest over lanes
      (``reference.kv_gap``).

    With ``control`` also the same two numbers for the float8 control put
    in the program's place: the gap of the token it puts first at the same
    positions, and its own keys and values."""
    import jax

    from bench import reference, weights

    w = weights.make(model, cell.seed, cell.deployment["dtype"])
    out: Dict[str, float] = {}
    n = min(cell.limits["sample_requests"], len(served))
    if n:
        rids = sorted(served)
        rng = np.random.default_rng([cell.seed, 1])
        longest = max(rids, key=lambda r: len(prompt_of[r]) + len(served[r]))
        rest = [r for r in rids if r != longest]
        pick = [longest] + list(rng.choice(rest, n - 1, replace=False))
        T = lane_length(shape)
        K = shape[1]
        tokens = np.zeros((n, T), np.int32)
        pos = np.zeros((n, K), np.int32)
        want = np.full((n, K), -1, np.int64)
        for i, r in enumerate(pick):
            seq = prompt_of[r] + served[r][:-1]
            tokens[i, :len(seq)] = seq
            P = len(prompt_of[r])
            pos[i, :len(served[r])] = np.arange(P - 1, P - 1 + len(served[r]))
            want[i, :len(served[r])] = served[r]
        valid = want >= 0
        ref = np.asarray(reference.logits(w, model, tokens, pos))
        best = ref.max(-1)
        got = np.take_along_axis(ref, np.maximum(want, 0)[..., None], -1)[..., 0]
        out.update(served_logit_gap=float((best - got)[valid].max()),
                   served_tokens_compared=int(valid.sum()))
        if control:
            ctl = np.asarray(reference.logits(w, model, tokens, pos,
                                              control=True)).argmax(-1)
            cg = best - np.take_along_axis(ref, ctl[..., None], -1)[..., 0]
            out["control_logit_gap"] = float(cg[valid].max())
        del ref
    if lanes["lengths"].any():
        args = (w, model, lanes["tokens"], lanes["lengths"], lanes["keys"],
                lanes["values"])
        out["kv_rel_gap"] = reference.kv_gap(*args)
        out["kv_lanes_compared"] = int((lanes["lengths"] > 0).sum())
        if control:
            out["control_kv_rel_gap"] = reference.kv_gap(*args, control=True)
    del w
    jax.clear_caches()
    return out


def lane_length(shape: Tuple[int, int]) -> int:
    """Positions the reference runs over: the traffic's longest request,
    rounded up to a multiple of 256."""
    return -(-shape[0] // 256) * 256


def checks(cell: Cell, numbers: Dict[str, float]) -> Dict[str, Dict]:
    """Each compared number beside its limit; ``ok`` when it is within."""
    out = {}
    for name, lim in cell.limits["limits"].items():
        if name in numbers:
            out[name] = {"value": numbers[name], "limit": lim["limit"]}
    return out


def finish(cell: Cell, dev: Dict, metrics: Dict, numbers: Dict,
           attempted: int, failed: int, breakdown: Optional[Dict] = None,
           device_extra: Optional[Dict] = None) -> Dict:
    """The result line; its last key holds each compared number beside its
    limit, and the same goes to standard error as the run's last lines."""
    chk = checks(cell, numbers)
    missing = sorted(set(cell.limits["limits"]) - set(chk))
    correct = (failed == 0 and not missing
               and all(c["value"] <= c["limit"] for c in chk.values()))
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics,
            "device": dict(dev, **(device_extra or {}))}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = chk
    for name in missing:
        print(f"check {name}: not read", file=sys.stderr)
    for name, c in chk.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return line


def traffic_shape(traffic) -> Tuple[int, int]:
    """The traffic's longest request and longest answer."""
    return traffic.max_total(), max(traffic.answers)


def check_lengths(cell: Cell, traffic) -> None:
    """Every request must finish by its answer budget, never by the cache's
    end: prompt plus answer is at most ``max_seq`` (the engine ends a lane
    once its next position would be ``max_seq - 1``, which a request of
    ``max_seq`` tokens reaches with its last token)."""
    if traffic.max_total() > cell.deployment["max_seq"]:
        raise ValueError(f"{cell.name}: longest request "
                         f"{traffic.max_total()} does not fit max_seq "
                         f"{cell.deployment['max_seq']}")


CHECK_SPAN = "handoff.verify"


def serving_s(rec: Recorder, w0: float, w1: float) -> float:
    """The window's seconds less the benchmark's own checks inside it
    (``CHECK_SPAN``), which a deployment would not pause for."""
    return (w1 - w0) - sum(rec.durations(CHECK_SPAN, w0, w1))


def report(cell: Cell, dev: Dict, model: Dict, rec: Recorder,
           prof: Optional[Profile], window: Tuple[float, float],
           setup_s: float, mem: Optional[int], served: Dict,
           traffic, lanes: Dict, attempted: int, failed: int,
           numbers: Dict, end_to_end: Optional[Dict] = None) -> Dict:
    """Metrics of the run (end to end, or per layer from the trace), then
    the reference check, then the result line."""
    from bench import trace as tr

    gc.collect()
    w0, w1 = window
    extra: Dict = {"memory_peak_bytes": mem}
    breakdown = None
    if cell.trace:
        t = prof.reduce()
        peak = None if cell.smoke else peaks(dev["kind"])
        metrics = per_layer(Run(cell, model, peak, t, prof.counters, rec,
                                window))
        extra.update(busy_s=tr.busy_s(t), window_s=t.window_s)
        breakdown = {"device_ops": tr.top_ops(t),
                     "idle_gaps": tr.idle_by_span(t)}
    else:
        e2e = dict(end_to_end or {}, setup_s=setup_s,
                   output_tok_s=rec.counters["tokens"] / serving_s(rec, w0, w1))
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    print(f"counts {json.dumps(rec.counters, sort_keys=True)} "
          f"window_s {w1 - w0!r}", file=sys.stderr)
    numbers = dict(numbers, **served_gaps(
        cell, model, served, traffic.prompt_of, traffic_shape(traffic),
        lanes))
    return finish(cell, dev, metrics, numbers, attempted, failed, breakdown,
                  extra)
