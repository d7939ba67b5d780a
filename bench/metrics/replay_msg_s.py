"""Replay rate, in logged messages per second, on the host clock over the
whole window: the messages the target replays after the pause over the
time of its ``replay`` spans."""


def read(run):
    w0, w1 = run.window
    t = sum(run.rec.durations("replay", w0, w1))
    n = run.rec.counters.get("replay_msgs", 0)
    return n / t if t > 0 and n else None
