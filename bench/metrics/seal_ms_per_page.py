"""Cache: device time of sealing one KV page, in ms: the mean run of the
sealer's program (``jit_seal``) on device 0 that starts in the profiled
window, times the sealer programs one page dispatches (``calls`` of the
``sched:seal`` spans there)."""

PROGRAM = "jit_seal"


def read(r):
    if not r.trace["device"]:
        return None
    calls = [s["args"]["calls"] for s in r.window_spans("sched:seal", r.p0,
                                                          r.p1)
             if "calls" in s["args"]]
    lo, hi = r.ns(r.p0), r.ns(r.p1)
    runs = [d for name, s, d in r.trace["device"][0]["modules"]
            if name.split("(")[0] == PROGRAM and lo <= s <= hi]
    if not calls or not runs:
        return None
    return sum(runs) / len(runs) / 1e6 * sum(calls) / len(calls)
