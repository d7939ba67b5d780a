"""Share of the chip's peak that whole pre-copy rounds reach, in %: the
roofline time of the work a round needs (reading each pushed state once
at peak HBM bandwidth, ``flops.codec_bytes``; it needs no FLOPs worth
counting) over the wall time of the ``precopy`` spans in the trace, host
work included. It bounds ``codec_roofline`` from below whatever kernels
a later change puts on the path."""
from bench import flops, trace


def read(run):
    rounds = trace.spans(run.trace, "precopy")
    nbytes = run.counters.get("push_state_bytes", 0)
    if not rounds or not nbytes or run.peak is None:
        return None
    wall = sum(s.end - s.start for s in rounds) / 1e9
    return 100.0 * flops.codec_bytes(nbytes) / run.peak["hbm_bytes_per_s"] / wall
