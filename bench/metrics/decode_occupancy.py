"""Scheduler: mean active slots of the decode lane, in % of ``n_slots``
(``n_active`` of the ``sched:decode`` spans in the window)."""


def read(r):
    sp = r.window_spans("sched:decode")
    if not sp:
        return None
    return 100.0 * sum(s["args"]["n_active"] for s in sp) / len(sp) / r.n_slots
