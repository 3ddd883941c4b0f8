"""Kernels: the decode lane's packed matmuls against their roofline, in %.

Least time, max(FLOPs / peak, bytes / HBM bandwidth), of every packed
linear of each decode call in the profiled window (active tokens only,
Eq.-1 bytes) over the device time of the ops under the engine's weight
variant scopes (``pallas:*``, ``xla:*``) in those calls' decode programs.
"""
from bench.lib import work
from bench.lib.tracing import MATMUL_FAMILIES


def read(r):
    if r.peaks is None:
        return None
    calls, dev = r.lane_device_ns(r.decode_calls(r.p0, r.p1), MATMUL_FAMILIES)
    if not calls or dev <= 0:
        return None
    cfg = r.cell.config
    least = sum(work.least_seconds(work.matmul(cfg, len(lens)), r.peaks)
                for _, _, lens in calls)
    return 100.0 * least / (dev / 1e9)
