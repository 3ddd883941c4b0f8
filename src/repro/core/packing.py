"""Weight encoding: mask header + payload (paper §IV-D.1, Fig. 5).

Compressed layout
-----------------
For every ``[1, w]`` block (per output channel) we store

* **mask header** — ``w`` bits, 1 = high precision (kept INT8), 0 = low.
* **hi payload**  — the ``n_high = w - n_low`` INT8 values, gathered in
  position order.
* **lo payload**  — the ``n_low`` low-precision codes, ``q`` bits each,
  bit-packed.  DLIQ: two's-complement ``q``-bit mantissa (dequant =
  ``mantissa << (8-q)``).  MIP2Q: top bit = sign, low ``q-1`` bits = barrel
  shift ``k`` (dequant = ``±2**k``).  Structured sparsity stores **no** lo
  payload — the mask alone determines the zeros (paper Eq. 2).

Because StruM fixes ``n_low`` per block, every compressed block has the same
byte length → tiles are uniformly addressable with no indirection tables.
This is the paper's "slowest-PE balance" property transplanted to TPU DMA
(DESIGN.md §2).

Compression ratios (bits per element, vs 8-bit uncompressed):

    r = (p(q-8) + 9) / 8        (Eq. 1, mixed payload)
    r = (9 - 8p) / 8            (Eq. 2, sparsity or q=1)

Our byte-aligned layout achieves Eq. 1 exactly whenever ``n_low·q`` is a
multiple of 8 (true for the paper's [1,16], p∈{0.25,0.5,0.75}, q=4) and is
within ``ceil`` padding of it otherwise; ``PackedStruM.achieved_ratio()``
reports the realized value.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import blocking
from repro.core.quantizers import QuantizedBlocks

__all__ = [
    "PackedStruM",
    "compression_ratio",
    "compression_ratio_sparsity",
    "field_dims",
    "pack",
    "decode_blocks",
    "decode_matrix",
    "dequantize",
]


def field_dims(w: int, n_low: int, q: int, method: str) -> tuple:
    """Per-block rows of the packed payload arrays: (mask, hi, lo).

    The single source of truth for the Fig.-5 field sizes — mirrored by
    :func:`pack` (actual arrays), ``apply.packed_payload_bytes`` (byte
    accounting), and ``models.quantize.packed_model_defs`` (dry-run defs).
    """
    mask_rows = -(-w // 8)                     # header bits, byte-padded
    hi_rows = w - n_low                        # int8 high payload
    lo_rows = 0 if method == "sparsity" else \
        -(-(n_low * q) // 8)                   # q-bit fields, byte-padded
    return mask_rows, hi_rows, lo_rows


def compression_ratio(p: float, q: int) -> float:
    """Paper Eq. 1 — compressed/uncompressed for the mixed payload."""
    return (p * (q - 8) + 9) / 8.0


def compression_ratio_sparsity(p: float) -> float:
    """Paper Eq. 2 — sparsity (or q=1): low values need no payload."""
    return (9 - 8 * p) / 8.0


class PackedStruM(NamedTuple):
    """Compressed StruM weight matrix (reduction dim K × out dim N).

    Shapes use ``nb = ceil(K/w)`` blocks; all payload arrays keep the output
    channel as the last (lane) dim for TPU-friendly tiling.
    """

    method: str              # 'sparsity' | 'dliq' | 'mip2q'
    w: int                   # block width (reduction elements per block)
    n_low: int               # low-precision values per block (= p*w, fixed)
    q: int                   # low payload bits (DLIQ q; MIP2Q ceil(log2(L+1))+1)
    L: int                   # MIP2Q max shift (unused otherwise)
    k_dim: int               # original (unpadded) K
    scale: jnp.ndarray       # (1, N) f32 — per-output-channel int8 scale
    mask: jnp.ndarray        # (nb, w//8, N) uint8 — header bits, 1 = high
    hi: jnp.ndarray          # (nb, n_high, N) int8 — high payload
    lo: jnp.ndarray          # (nb, ceil(n_low*q/8), N) uint8 — low payload

    @property
    def n_high(self) -> int:
        return self.w - self.n_low

    @property
    def n_out(self) -> int:
        return self.scale.shape[-1]

    def payload_bytes(self) -> int:
        return int(self.mask.size + self.hi.size + self.lo.size)

    def achieved_ratio(self) -> float:
        """Realized compressed/uncompressed-int8 byte ratio (excl. scales)."""
        nb = self.mask.shape[0]
        return self.payload_bytes() / float(nb * self.w * self.n_out)


def _pack_bits_axis(bits: jnp.ndarray, axis: int = 1) -> jnp.ndarray:
    """Pack a bool/0-1 array into uint8 along ``axis`` (LSB-first)."""
    n = bits.shape[axis]
    pad = (-n) % 8
    if pad:
        widths = [(0, 0)] * bits.ndim
        widths[axis] = (0, pad)
        bits = jnp.pad(bits, widths)
    shape = list(bits.shape)
    shape[axis : axis + 1] = [shape[axis] // 8, 8]
    b = bits.astype(jnp.uint8).reshape(shape)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8)).reshape(
        (1,) * (axis + 1) + (8,) + (1,) * (bits.ndim - axis - 1)
    )
    return jnp.sum(b * weights, axis=axis + 1, dtype=jnp.uint8)


def _unpack_bits_axis(packed: jnp.ndarray, n: int, axis: int = 1) -> jnp.ndarray:
    """Inverse of :func:`_pack_bits_axis`; returns bool with size ``n``."""
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(
        (1,) * (axis + 1) + (8,) + (1,) * (packed.ndim - axis - 1)
    )
    bits = (jnp.expand_dims(packed, axis + 1) >> shifts) & jnp.uint8(1)
    shape = list(packed.shape)
    shape[axis] = shape[axis] * 8
    bits = bits.reshape(shape)
    idx = [slice(None)] * bits.ndim
    idx[axis] = slice(0, n)
    return bits[tuple(idx)].astype(bool)


def _pack_fields(codes: jnp.ndarray, q: int) -> jnp.ndarray:
    """Bit-pack unsigned q-bit fields along axis 1: (nb, nl, N) -> (nb, B, N)."""
    nb, nl, n = codes.shape
    if nl == 0:
        return jnp.zeros((nb, 0, n), jnp.uint8)
    shifts = jnp.arange(q, dtype=jnp.uint8)
    bits = (codes[:, :, None, :].astype(jnp.uint8) >> shifts[None, None, :, None]) & 1
    bits = bits.reshape(nb, nl * q, n)
    return _pack_bits_axis(bits, axis=1)


def _unpack_fields(packed: jnp.ndarray, nl: int, q: int) -> jnp.ndarray:
    """Inverse of :func:`_pack_fields`; returns uint8 codes (nb, nl, N)."""
    nb, _, n = packed.shape
    if nl == 0:
        return jnp.zeros((nb, 0, n), jnp.uint8)
    bits = _unpack_bits_axis(packed, nl * q, axis=1).reshape(nb, nl, q, n)
    weights = (jnp.uint8(1) << jnp.arange(q, dtype=jnp.uint8))[None, None, :, None]
    return jnp.sum(bits.astype(jnp.uint8) * weights, axis=2, dtype=jnp.uint8)


def _gather_compact(values: jnp.ndarray, mask: jnp.ndarray, count: int) -> jnp.ndarray:
    """Gather ``values`` where ``mask`` into a dense (nb, count, N) array,
    preserving position order — the payload layout of Fig. 5.

    Payload row ``r`` is the masked sum over the block of the one value
    whose rank among the masked positions is ``r`` (0 where there is
    none; masked positions past ``count`` are dropped).  One reduction per
    row (``count <= w <= 32``) instead of a scatter, which on a TPU v5e took
    nearly all of a KV page seal's time (the seal compacts every element of
    the page).
    """
    nb, w, n = values.shape
    if count == 0:
        return jnp.zeros((nb, 0, n), values.dtype)
    # rank of each position among the masked ones
    rank = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    rows = [jnp.sum(jnp.where(mask & (rank == r), values, 0), axis=1,
                    dtype=values.dtype) for r in range(count)]
    return jnp.stack(rows, axis=1)


def _scatter_expand(payload: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`_gather_compact`: place payload back at mask slots.

    Positions where ``mask`` is False get 0.  One select per payload row
    (``count <= w <= 32``) instead of a gather along the block axis: XLA
    fuses the selects into one elementwise pass, where a TPU gather over
    every weight element would dominate the dequantize.
    """
    nb, w, n = mask.shape
    count = payload.shape[1]
    out = jnp.zeros((nb, w, n), payload.dtype)
    rank = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    for r in range(count):
        out = jnp.where(mask & (rank == r), payload[:, r:r + 1, :], out)
    return out


def pack(qb: QuantizedBlocks, *, method: str, scale: jnp.ndarray, k_dim: int,
         n_low: int, q: int, L: int) -> PackedStruM:
    """Encode set-quantized blocks into the compressed format (Fig. 5).

    ``n_low`` is the structural per-block low count (p·w) — a static int, so
    payload shapes are known at trace time (the "uniform DMA tile" property).
    """
    values, low, low_code = qb
    nb, w, n = values.shape
    high = ~low
    n_high = w - n_low

    mask_bytes = _pack_bits_axis(high, axis=1)
    hi = _gather_compact(values.astype(jnp.int8), high, n_high)
    if method == "sparsity":
        lo = jnp.zeros((nb, 0, n), jnp.uint8)
    else:
        # store codes as unsigned q-bit fields
        code_u = (low_code.astype(jnp.int32) & ((1 << q) - 1)).astype(jnp.uint8)
        if method == "mip2q":
            # low_code = sign*(k+1): re-encode as [sign | k] fields
            k = jnp.abs(low_code) - 1
            sgn = (low_code < 0).astype(jnp.int32)
            code_u = jnp.where(
                low, (sgn << (q - 1)) | jnp.clip(k, 0, (1 << (q - 1)) - 1), 0
            ).astype(jnp.uint8)
        lo_codes = _gather_compact(code_u, low, n_low)
        lo = _pack_fields(lo_codes, q)
    return PackedStruM(method, w, n_low, q, L, k_dim,
                       scale.reshape(1, -1).astype(jnp.float32),
                       mask_bytes, hi, lo)


def _decode_low_values(codes: jnp.ndarray, method: str, q: int) -> jnp.ndarray:
    """q-bit field -> int32 value on the int8 grid."""
    c = codes.astype(jnp.int32)
    if method == "sparsity":
        return jnp.zeros_like(c)
    if method == "dliq":
        # sign-extend q-bit two's complement, then shift-left (8-q)
        sign_bit = 1 << (q - 1)
        mant = (c ^ sign_bit) - sign_bit
        return mant << (8 - q)
    if method == "mip2q":
        sgn = 1 - 2 * (c >> (q - 1))
        k = c & ((1 << (q - 1)) - 1)
        return sgn * (1 << k)
    raise ValueError(method)


def decode_blocks(p: PackedStruM) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decompress to blocked int32 values + high-mask (nb, w, N)."""
    high = _unpack_bits_axis(p.mask, p.w, axis=1)
    hi_vals = _scatter_expand(p.hi.astype(jnp.int32), high)
    if p.method == "sparsity" or p.n_low == 0:
        lo_vals = jnp.zeros_like(hi_vals)
    else:
        codes = _unpack_fields(p.lo, p.n_low, p.q)
        lo_dec = _decode_low_values(codes, p.method, p.q)
        lo_vals = _scatter_expand(lo_dec, ~high)
    return jnp.where(high, hi_vals, lo_vals), high


def decode_matrix(p: PackedStruM) -> jnp.ndarray:
    """Decompress to the (K, N) int32 value matrix (int8 grid)."""
    vals, _ = decode_blocks(p)
    return blocking.from_blocks(vals, p.k_dim)


def dequantize(p: PackedStruM, dtype=jnp.float32) -> jnp.ndarray:
    """Decompress to real-valued weights: values · per-channel scale."""
    return (decode_matrix(p).astype(jnp.float32) * p.scale).astype(dtype)
