"""Scheduler: mean host time of a tick, in ms: the duration of each
``sched:step`` span in the window less what its ``sched:sync`` spans (the
host blocked on a device value) cover."""


def read(r):
    steps = r.window_spans("sched:step")
    syncs = r.window_spans("sched:sync")
    if not steps or not syncs:
        return None
    host = 0.0
    for st in steps:
        waited = sum(s["end"] - s["start"] for s in syncs
                     if st["start"] <= s["start"] and s["end"] <= st["end"])
        host += st["end"] - st["start"] - waited
    return 1e3 * host / len(steps)
