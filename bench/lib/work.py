"""Operations and bytes of each kernel call, computed from the model's
shapes and the codecs' stored formats (paper Eq. 1), never from the trace.

The packed layout of a ``[1, w]`` block holds a header of ``w`` bits, the
``w - n_low`` high values as int8 and the ``n_low`` low values as ``q``-bit
fields; MIP2Q's ``q`` is ``ceil(log2(L + 1)) + 1`` (sign and shift).  Each
output channel adds one float32 scale per matrix, and per page for KV.
"""
from __future__ import annotations

import math

ACT_BYTES = 2          # bfloat16 activations in and out of each matmul


def payload_bits(codec: dict) -> int:
    """Bits that one ``[1, w]`` block occupies."""
    w = int(codec["w"])
    n_low = round(codec["p"] * w)
    if codec["method"] == "mip2q":
        q = math.ceil(math.log2(codec["L"] + 1)) + 1 if codec["L"] > 0 else 1
    else:
        q = int(codec["q"])
    return 8 * (-(-w // 8) + (w - n_low) + (-(-(n_low * q) // 8)))


def bytes_per_element(codec: dict) -> float:
    return payload_bits(codec) / 8 / int(codec["w"])


def _arch(config: dict):
    from bench.lib.harness import reference_module
    ref = reference_module(config)
    return ref, ref.Arch(config)


def matmuls(config: dict) -> list:
    """(K, N, layers) of every linear served through the weight codec."""
    ref, _ = _arch(config)
    packed = ref.packed_names(config)
    return [(lf.shape[0], lf.shape[1], lf.layers)
            for lf in ref.leaves(config) if lf.name in packed]


def matmul(config: dict, m: int) -> tuple:
    """(flops, bytes) of the packed linears of one lane call over ``m``
    active tokens."""
    bpe = bytes_per_element(config["serve"]["weights"])
    flops = nbytes = 0.0
    for k, n, layers in matmuls(config):
        flops += layers * 2.0 * m * k * n
        nbytes += layers * (k * n * bpe + 4.0 * n + ACT_BYTES * m * (k + n))
    return flops, nbytes


def attention(config: dict, lens: list) -> tuple:
    """(flops, bytes) of the sealed-page attention of one decode call: for
    each active slot with cache length ``c``, the ``(c // page) * page``
    positions already sealed, read as packed pages with their scales."""
    _, a = _arch(config)
    page = a.page
    f = a.nkv * a.hd
    bpe = bytes_per_element(config["serve"]["kv"])
    page_bytes = page * f * bpe + 4.0 * f
    flops = nbytes = 0.0
    for c in lens:
        n = (int(c) // page) * page
        flops += a.layers * 4.0 * a.nh * a.hd * n
        nbytes += a.layers * 2 * (n // page) * page_bytes
    return flops, nbytes


def token_flops(config: dict, ctx: int) -> float:
    """Model FLOPs of one token that attends over ``ctx`` positions: two per
    weight of every linear and of the output head, plus QK and PV."""
    _, a = _arch(config)
    linear = sum(layers * k * n for k, n, layers in matmuls(config))
    return (2.0 * (linear + a.d * a.vocab)
            + a.layers * 4.0 * a.nh * a.hd * ctx)


def least_seconds(work: tuple, peaks: dict) -> float:
    flops, nbytes = work
    return max(flops / peaks["bf16_flops_s"], nbytes / peaks["hbm_bytes_s"])
