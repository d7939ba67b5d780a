"""Model FLOP utilization of the traced window, in %: the forward work of
the token passes served to users (prompt tokens and sampled tokens of
active lanes, not replays), from ``bench/flops.py``, over the window and
the chip's bf16 peak."""
from bench import flops


def read(run):
    n = run.counters.get("useful_n", 0)
    if not n or run.peak is None:
        return None
    f = flops.step_flops(run.model, n, run.counters["useful_ctx"])
    return 100.0 * f / run.trace.window_s / run.peak["bf16_flops_per_s"]
