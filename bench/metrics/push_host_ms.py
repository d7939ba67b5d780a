"""Host time per push, in milliseconds: each ``registry.push`` span less
the device-busy time inside it (parent rebuild, device-to-host copy, RLE,
sha256, the store and the manifest)."""
from bench import trace


def read(run):
    pushes = trace.spans(run.trace, "registry.push")
    if not pushes or not run.trace.ops:
        return None
    b = trace.busy(run.trace)
    host = [(s.end - s.start) - trace.overlap(b, s.start, s.end)
            for s in pushes]
    return sum(host) / len(host) / 1e6
