"""Spans and counters at the serving path's layer boundaries.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: while the profiler
runs, it lands on the profiler's host plane, on the same clock as the
device plane, so a reader can take a span's device-busy time from the
device's own events. While the profiler is off it costs about a
microsecond and records nothing. No span forces a device sync: a span
ends where the host's work ends, and device time comes from the trace.

``count(name, n)`` adds to one process-wide table of the counters in
``COUNTERS``; ``counters()`` snapshots it. A reader takes the difference
of two snapshots.

Compiles are counted from ``jax.monitoring`` events: ``jit.compiles``
counts each XLA executable the process builds or loads (one per new
program or new argument signature, none for a call served from the
in-memory cache), ``jit.cache_loads`` those of them read from the
persistent compilation cache.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax.monitoring
from jax.profiler import TraceAnnotation

# every span the program emits; the benchmark keeps these host events
SPANS = (
    "engine.step",           # one batched decode step, before admissions
    "engine.step.dispatch",  # the jitted step, until the call returns
    "engine.sync",           # queued steps' sampled tokens read on the host
    "engine.admit",          # one request's prompt folded into its slot
    "engine.load",           # a restored state put on the device
    "push.parent",           # the parent's chunks rebuilt from the store
    "push.device",           # fingerprint or fused kernel, outputs on host
    "push.serialize",        # a device leaf copied whole to the host
    "push.rle",              # one chunk delta-encoded on the host
    "push.store",            # one chunk hashed and written
    "push.manifest",         # the manifest hashed and written
    "pull.decode",           # one leaf's chunks read and decoded
    "pull.assemble",         # one leaf's array built from its bytes
)

COUNTERS = (
    "engine.steps",          # batched decode steps
    "engine.lanes",          # lanes fed a token, summed over steps
    "engine.syncs",          # syncs that read queued steps' tokens
    "moe.expert_loads",      # (layer, expert) weight fetches of the decode
                             # step's routed experts, summed over steps
    "restore.h2d_bytes",     # host bytes a restore puts on the device
    "engine.snapshot_bytes", # cache bytes copied on the device for
                             # state_tree and for load_state's device leaves
    "push.h2d_bytes",        # host bytes a push hands to the device
    "push.d2h_bytes",        # device bytes a push copies to the host
    "push.parent_bytes",     # parent bytes rebuilt from the store
    "push.hashed_bytes",     # bytes sha256-hashed into the chunk store
    "pull.read_bytes",       # stored bytes a pull reads
    "jit.compiles",          # XLA executables built or loaded
    "jit.cache_loads",       # of those, read from the persistent cache
)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_counters: Dict[str, int] = dict.fromkeys(COUNTERS, 0)
_lock = threading.Lock()


def span(name: str, **args) -> TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``), with ``args`` as
    its metadata in the trace."""
    return TraceAnnotation(name, **args)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (one of ``COUNTERS``)."""
    with _lock:
        _counters[name] += n


def counters() -> Dict[str, int]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counters)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
        count("jit.compiles")


def _on_event(event: str, **kwargs) -> None:
    if event == CACHE_HIT_EVENT:
        count("jit.cache_loads")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
