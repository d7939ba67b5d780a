"""Token passes per decode step in the traced part: how many of the
engine's lanes did served work in each batched step. One admission at a
time, one prompt token per step, bounds it from above."""


def read(run):
    steps = sum(1 for e in (run.trace.modules[0] if run.trace.modules else [])
                if "_decode_all" in e.name)
    n = run.counters.get("all_n", 0)
    return n / steps if steps and n else None
