"""Model step: mean device time of one prefill chunk, in ms: the runs of the
prefill lane's program (``jit_prefill_chunk_step``) on device 0 that start
in the profiled window."""

PROGRAM = "jit_prefill_chunk_step"


def read(r):
    if not r.trace["device"]:
        return None
    lo, hi = r.ns(r.p0), r.ns(r.p1)
    runs = [d for name, s, d in r.trace["device"][0]["modules"]
            if name.split("(")[0] == PROGRAM and lo <= s <= hi]
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6
