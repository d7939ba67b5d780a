"""Device time per run of the engine's jitted decode step (``_decode_all``
module events in the trace), in milliseconds: the engine layer's cost of
one batched token step."""


def read(run):
    runs = [e.end - e.start for e in run.trace.modules[0]
            if "_decode_all" in e.name] if run.trace.modules else []
    return sum(runs) / len(runs) / 1e6 if runs else None
