"""Kernels: share of the KV pages the decode lane's sealed-page attention
gathers that hold sealed context, in %: the sum of ``pages_valid`` over the
sum of ``pages_gathered`` of the ``sched:decode`` spans in the window."""


def read(r):
    sp = [s["args"] for s in r.window_spans("sched:decode")
          if "pages_gathered" in s["args"]]
    gathered = sum(a["pages_gathered"] for a in sp)
    if gathered <= 0:
        return None
    return 100.0 * sum(a["pages_valid"] for a in sp) / gathered
