"""Kernels: the decode lane's LM head against its roofline, in %.

Least time of the head in each decode call of the profiled window, over the
device time of the ops under the program's ``head:*`` scope in that call's
decode program.  The head is one bf16 matmul, tied or untied alike: its
weight is read whole (``d x vocab_rows`` bf16), the active tokens' hidden
states come in as bf16 and their logits go out as float32, and it does
``2 m d V`` FLOPs for ``m`` active tokens.  A program whose head carries no
such scope reads nothing.
"""
from bench.lib import work
from bench.lib.harness import reference_module

FAMILIES = ("head",)
BF16, F32 = 2, 4       # bytes: the weight and hidden states; the logits


def head_work(config: dict, m: int) -> tuple:
    """(flops, bytes) of the LM head over ``m`` active tokens."""
    a = reference_module(config).Arch(config)
    flops = 2.0 * m * a.d * a.vocab
    nbytes = (a.d * a.vocab_rows * BF16 + m * a.d * BF16
              + m * a.vocab_rows * F32)
    return flops, float(nbytes)


def read(r):
    if r.peaks is None:
        return None
    calls, dev = r.lane_device_ns(r.decode_calls(r.p0, r.p1), FAMILIES)
    if not calls or dev <= 0:
        return None
    cfg = r.cell.config
    least = sum(work.least_seconds(head_work(cfg, len(lens)), r.peaks)
                for _, _, lens in calls)
    return 100.0 * least / (dev / 1e9)
