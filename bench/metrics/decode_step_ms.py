"""Model step: mean wall time of the ``sched:decode`` spans in the window.
The span ends in the host's argmax of the step's logits, so it covers the
device step."""


def read(r):
    sp = r.window_spans("sched:decode")
    if not sp:
        return None
    return 1e3 * sum(s["end"] - s["start"] for s in sp) / len(sp)
