"""Share of its roofline that decode attention reaches, in %: the least
time for the K/V bytes of the positions each active lane attends (not the
whole cache), plus queries and outputs, over the device time of the
decode-attention kernel in the trace. Idle lanes are no served work."""
from bench import flops

KERNEL = "decode_attention"


def read(run):
    n = run.counters.get("all_n", 0)
    busy = sum(e.end - e.start for e in (run.trace.ops[0] if run.trace.ops
                                          else []) if KERNEL in e.name)
    if not n or not busy or run.peak is None:
        return None
    ctx = run.counters["all_ctx"]
    least = flops.least_seconds(
        flops.attention_flops(run.model, ctx),
        flops.attention_bytes(run.model, n, ctx, 2), run.peak)
    return 100.0 * least / (busy / 1e9)
