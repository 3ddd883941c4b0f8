"""Scheduler: mean number of scheduler ticks from a request's admission to
its first token (lifecycle events placed on the ``tick`` of the
``sched:step`` span that holds them), for requests whose first token came
in the window."""


def _tick(steps, t):
    for s in steps:
        if s["start"] <= t <= s["end"]:
            return s["args"]["tick"]
    return None


def read(r):
    steps = [s for s in r.spans if s["name"] == "sched:step"]
    ticks = []
    for ev in r.events.values():
        adm = [t for st, t, _ in ev if st == "admitted"]
        first = [t for st, t, _ in ev if st == "first_token"]
        if not adm or not first or not r.t_open < first[0] <= r.t_close:
            continue
        a, b = _tick(steps, adm[0]), _tick(steps, first[0])
        if a is not None and b is not None:
            ticks.append(b - a + 1)
    if not ticks:
        return None
    return sum(ticks) / len(ticks)
