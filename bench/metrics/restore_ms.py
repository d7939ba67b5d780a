"""Restore time per handoff, in milliseconds, on the host clock over the
whole window: ``pull_image`` (chain decode) plus ``load_state`` until the
cache is resident on the device."""


def read(run):
    w0, w1 = run.window
    pulls = run.rec.durations("registry.pull", w0, w1)
    loads = run.rec.durations("load_state", w0, w1)
    if not pulls or len(pulls) != len(loads):
        return None
    return 1e3 * (sum(pulls) + sum(loads)) / len(pulls)
