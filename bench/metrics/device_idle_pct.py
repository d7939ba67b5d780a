"""Share of the traced window in which no operation ran on the device,
in %."""
from bench import trace


def read(run):
    w = run.trace.window_s
    if not run.trace.ops or w <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / w)
