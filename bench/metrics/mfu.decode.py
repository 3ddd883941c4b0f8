"""Whole step: model FLOPs of every token processed in the window, prompt
and output alike (two per weight of the linears and the head, plus
attention over its context), over the window times the chip's bf16 peak,
in %."""
from bench.lib import work


def read(r):
    if r.peaks is None:
        return None
    cfg = r.cell.config
    flops = 0.0
    for _, _, lens in r.decode_calls():
        flops += sum(work.token_flops(cfg, c + 1) for c in lens)
    for start, valid in r.prefill_chunks():
        flops += sum(work.token_flops(cfg, start + i + 1)
                     for i in range(valid))
    if flops <= 0:
        return None
    return 100.0 * flops / ((r.t_close - r.t_open) * r.peaks["bf16_flops_s"])
