"""Share of its roofline that the pre-copy's device pass reaches, in %:
the least time to read each pushed state once (``flops.codec_bytes``)
over the device-busy time inside the ``registry.push`` spans, where the
fingerprint and fused XOR kernels run."""
from bench import flops, trace


def read(run):
    pushes = trace.spans(run.trace, "registry.push")
    nbytes = run.counters.get("push_state_bytes", 0)
    if not pushes or not nbytes or run.peak is None or not run.trace.ops:
        return None
    b = trace.busy(run.trace)
    dev = sum(trace.overlap(b, s.start, s.end) for s in pushes) / 1e9
    if dev <= 0:
        return None
    return 100.0 * flops.codec_bytes(nbytes) / run.peak["hbm_bytes_per_s"] / dev
