"""Device: share of the profiled window in which no operation ran on the
device (1 - union of op intervals / window), in %."""


def read(r):
    if r.window_s <= 0 or not r.trace["device"]:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
