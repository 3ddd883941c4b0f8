"""Kernels: the decode lane's sealed-page attention against its roofline,
in %.

Least time of the valid context only (each active slot's sealed positions,
packed KV bytes with page scales; QK and PV FLOPs) over the device time of
the ops under the engine's ``attn:*`` scope (page gather and the paged
attention kernel) in the decode programs of the profiled window.
"""
from bench.lib import work
from bench.lib.tracing import ATTN_FAMILIES


def read(r):
    if r.peaks is None:
        return None
    calls, dev = r.lane_device_ns(r.decode_calls(r.p0, r.p1), ATTN_FAMILIES)
    if not calls or dev <= 0:
        return None
    cfg = r.cell.config
    least = sum(work.least_seconds(work.attention(cfg, lens), r.peaks)
                for _, _, lens in calls)
    return 100.0 * least / (dev / 1e9)
