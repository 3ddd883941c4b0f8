"""Pallas TPU kernel: tiled matmul over *compressed* StruM weights.

This is the TPU-native realization of the paper's accelerated PE (§IV-D.2,
Fig. 6).  On FlexNN the mask header routes values to INT8 multipliers vs
barrel shifters; on TPU the win is the **memory roofline**: the kernel
streams the packed form (mask header + mixed payload — r× fewer HBM bytes,
paper Eq. 1/2) into VMEM and dequantizes there, so the MXU sees ordinary
bf16/f32 tiles while HBM traffic shrinks by exactly the paper's ratio.

Because StruM fixes ``n_low`` per ``[1, w]`` block, every compressed tile has
a static shape — BlockSpecs address the payload with plain block indices, no
indirection tables (the paper's "slowest-PE balance" property, here:
uniform DMA descriptors).

Decode strategy inside the kernel (vectorized, gather-free, int32 bit
arithmetic throughout — Mosaic's vector shifts and iotas are 32-bit):
  1. the ``w // 8`` mask bytes of each block form one int32 word; a
     position's bit and its rank among the high positions (a popcount of
     the word's lower bits) come from shifts and ands against a position
     iota — no prefix scan along the block,
  2. each position gets a slot in the concatenated payload ``hi ++ lo``,
     and the payload rows land by one select per row (w <= 32) — no dynamic
     gather and no (w, count) one-hot intermediate,
  3. low codes decoded per method:  DLIQ  mantissa << (8-q)  (the INT4xINT8
     multiplier path),  MIP2Q  +-(1 << k)  (the barrel-shifter path),
  4. f32 (values * per-channel scale) tile -> MXU dot, f32 accumulation.

Every kernel compiles through Mosaic for TPU v5e at the widths the models
serve with (``tests/test_tpu_compile.py``, against a described chip) and
matches ``ref.strum_matmul_ref`` in interpret mode on CPU
(``tests/test_kernels.py``); ``chip_smoke.py`` serves OLMo-1B through them
on a real chip.

Besides the general ``strum_matmul_pallas`` (the one-hot scatter decode that
handles every method × n_low), two *specialized* lowerings exist for the
schedule extremes the autotuner actually emits — they stream fewer operands
and skip the mask/rank machinery entirely:

``strum_matmul_pallas_maskfree``  p = 1.0 (n_low == w): every value is low
                                  precision, so the mask is all-zeros and the
                                  lo payload is already in position order —
                                  decode is unpack-fields → method decode →
                                  place by position.  No mask or hi stream.
``strum_matmul_pallas_dense``     n_low == 0: every value is INT8 and the hi
                                  payload is the block in position order —
                                  decode is a reshape + scale.  No mask or lo
                                  stream, and no ``w % 8`` constraint.

The **grouped** family (``strum_matmul_pallas_grouped`` and its
maskfree/dense twins) batches the same decode over a *leading* stack axis —
one grid dimension per expert/scan group, so MoE expert stacks execute
compressed end-to-end instead of falling back to dequantize + XLA einsum.
Every group streams its own packed payload tile (same uniform DMA
descriptors: StruM's fixed ``n_low`` keeps block shapes static across
experts), and the decode helpers (`_decode_tile`, `_decode_tile_maskfree`)
are shared with the 2-D kernels verbatim.

Selection between these lives in :mod:`repro.engine.registry` — the kernels
themselves stay selection-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scoped(name):
    """Run the lowering under ``jax.named_scope(name)`` so each Pallas
    variant is attributable in XLA/Perfetto profiles.  named_scope is
    trace-time metadata — zero runtime cost, works under jit/vmap/scan."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


__all__ = [
    "strum_matmul_pallas",
    "strum_matmul_pallas_maskfree",
    "strum_matmul_pallas_dense",
    "strum_matmul_pallas_histream",
    "strum_matmul_pallas_maskfree_p",
    "strum_matmul_pallas_grouped",
    "strum_matmul_pallas_grouped_maskfree",
    "strum_matmul_pallas_grouped_dense",
]


# The decode helpers below keep every intermediate 3-D with the block axis
# second-minor: payload rows are sliced as (bnb, 1, bn) and broadcast
# against (bnb, w, bn) position tiles, so Mosaic never relayouts a row into
# a different tiling.  All bit arithmetic is int32 (Mosaic's vector shifts
# and iotas are 32-bit; uint8 payloads are widened first).

def _popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Set bits of a non-negative int32 (SWAR; shifts, ands and adds only)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    x = x + (x >> 8)
    x = x + (x >> 16)
    return x & 0x3F


def _mask_rank(mask_u8: jnp.ndarray, w: int):
    """(bnb, w//8, bn) uint8 mask header -> ``(high, rank)``, both (bnb, w, bn).

    ``high`` is the LSB-first bit of each position; ``rank`` counts the high
    positions before it in its block.  The header's ``w // 8 <= 4`` bytes
    form one int32 word per (block, column), so the in-block rank is a
    popcount of the word's lower bits — no prefix scan along the block.
    """
    bnb, mb, bn = mask_u8.shape
    m = mask_u8.astype(jnp.int32)
    word = m[:, 0:1, :]
    for b in range(1, mb):
        word = word | (m[:, b:b + 1, :] << (8 * b))           # (bnb, 1, bn)
    pos = lax.broadcasted_iota(jnp.int32, (bnb, w, bn), 1)
    high = ((word >> pos) & 1) == 1
    rank = _popcount(word & ((1 << pos) - 1))     # bit 31 never survives
    return high, rank


def _unpack_fields(lo_u8: jnp.ndarray, n_low: int, q: int) -> list:
    """(bnb, ceil(n_low*q/8), bn) uint8 -> ``n_low`` (bnb, 1, bn) int32 codes.

    Field ``j`` holds bits ``[j*q, (j+1)*q)`` of the LSB-first byte stream,
    so it reads one byte, or two where it straddles a byte boundary.
    """
    lo = lo_u8.astype(jnp.int32)
    out = []
    for j in range(n_low):
        byte, shift = divmod(j * q, 8)
        v = lo[:, byte:byte + 1, :] >> shift
        if shift + q > 8:
            v = v | (lo[:, byte + 1:byte + 2, :] << (8 - shift))
        out.append(v & ((1 << q) - 1))
    return out


def _decode_low(codes: jnp.ndarray, method: str, q: int) -> jnp.ndarray:
    """q-bit payload fields -> f32 values on the int8 grid."""
    if method == "sparsity":
        return jnp.zeros(codes.shape, jnp.float32)
    if method == "dliq":
        sign_bit = 1 << (q - 1)
        mant = (codes ^ sign_bit) - sign_bit        # sign-extend q bits
        return (mant << (8 - q)).astype(jnp.float32)
    if method == "mip2q":
        sgn = 1 - 2 * (codes >> (q - 1))
        k = codes & ((1 << (q - 1)) - 1)
        return (sgn * (1 << k)).astype(jnp.float32)  # the barrel shift ±2**k
    raise ValueError(method)


def _place(rows: list, slot: jnp.ndarray) -> jnp.ndarray:
    """out[b, i, n] = rows[slot[b, i, n]][b, 0, n]; out-of-range slots -> 0.

    A select per payload row (w <= 32): the one-hot scatter without a
    dynamic gather and without a (w, count) one-hot intermediate.
    """
    out = jnp.zeros(slot.shape, jnp.float32)
    for s, row in enumerate(rows):
        out = jnp.where(slot == s, row, out)
    return out


def _decode_tile(mask_u8, hi_i8, lo_u8, scale_f32, *, w, n_low, q, method):
    """Decompress one (bk, bn) weight tile in VMEM; returns f32."""
    vals = _decode_blocks(mask_u8, hi_i8, lo_u8, w=w, n_low=n_low, q=q,
                          method=method)
    bnb, _, bn = vals.shape
    return vals.reshape(bnb * w, bn) * scale_f32             # (bk, bn) f32


def _decode_blocks(mask_u8, hi_i8, lo_u8, *, w, n_low, q, method):
    """Unscaled (bnb, w, bn) f32 values of ``bnb`` packed blocks.

    Every position gets a slot in the concatenated payload ``hi ++ lo``:
    its rank among the high positions, or ``n_high`` plus its rank among
    the low ones.  Sparsity's low positions get no slot and decode to 0.
    """
    n_high = w - n_low
    high, rank = _mask_rank(mask_u8, w)                      # (bnb, w, bn)
    hi = hi_i8.astype(jnp.float32)
    rows = [hi[:, i:i + 1, :] for i in range(n_high)]
    if method == "sparsity" or n_low == 0:
        slot = jnp.where(high, rank, w)
    else:
        pos = lax.broadcasted_iota(jnp.int32, high.shape, 1)
        slot = jnp.where(high, rank, n_high + pos - rank)
        rows += [_decode_low(c, method, q)
                 for c in _unpack_fields(lo_u8, n_low, q)]
    return _place(rows, slot)


def _decode_tile_maskfree(lo_u8, scale_f32, *, w, q, method):
    """p = 1.0 decode: the lo fields are the whole block in position order."""
    vals = _decode_blocks_maskfree(lo_u8, w=w, q=q, method=method)
    bnb, _, bn = vals.shape
    return vals.reshape(bnb * w, bn) * scale_f32


def _decode_blocks_maskfree(lo_u8, *, w, q, method):
    """Unscaled (bnb, w, bn) f32 values of ``bnb`` p = 1.0 blocks."""
    rows = [_decode_low(c, method, q) for c in _unpack_fields(lo_u8, w, q)]
    bnb, _, bn = lo_u8.shape
    return _place(rows, lax.broadcasted_iota(jnp.int32, (bnb, w, bn), 1))


# ------------------------------------------------------------------- 2-D --
#
# Grid (M/bm, N/BN, K/BK).  A grid step streams one (BK, BN) block of each
# packed field and folds it into the resident (bm, BN) f32 output in
# (sub_k, sub_n) sub-tiles, each decoded and multiplied as one tile.  A
# step costs a fixed ~0.07 us on v5e whatever it holds, so the block is
# chosen large (:func:`block_shape`), while the sub-tile keeps the decode's
# working set and Mosaic's unrolled code at one tile's size.  Each output
# column sums its K sub-tiles in ascending order, one f32 dot at a time:
# the order of a grid over (sub_k, sub_n) tiles, so the results are
# bit-equal to it.

#: the tile one decode and f32 dot cover, rows x K x N (clamped to small
#: axes by ``ops.grid_plan``); a grid step's block is a whole number of them
SUB_M, SUB_K, SUB_N = 128, 256, 256
#: packed weights a grid step aims for (0.92 MB of Eq.-1 bytes at p = 0.5)
STEP_WEIGHTS = 1 << 20
#: bound on :func:`_vmem_bytes`; the compiler is given ``VMEM_LIMIT``
#: (v5e has 128 MiB of VMEM per core)
VMEM_BUDGET = 24 << 20
VMEM_LIMIT = 48 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_bytes(bm, bk, bn, w, field_rows, x_bytes, sub_k, sub_n) -> int:
    """Estimated VMEM of one grid step: the double-buffered x, payload,
    scale and output blocks (8-bit rows tile by 4 sublanes, the x block's
    rows by 8 times its packing, a 1-row scale by 8) and a dozen f32
    sub-tiles of decode temporaries."""
    payload = bk // w * sum(_round_up(r, 4) for r in field_rows) * bn
    x = _round_up(bm, 8 * (4 // x_bytes)) * bk * x_bytes
    blocks = payload + x + 8 * bn * 4 + _round_up(bm, 8) * bn * 4
    return 2 * blocks + 12 * sub_k * sub_n * 4


def _divisor_multiples(dim: int, step: int):
    """Multiples of ``step`` that divide ``dim`` (itself a multiple)."""
    return [step * d for d in range(1, dim // step + 1)
            if (dim // step) % d == 0]


def block_shape(bm: int, k_pad: int, n_pad: int, w: int, field_rows, *,
                sub_k: int, sub_n: int, x_bytes: int = 2) -> tuple[int, int]:
    """``(BK, BN)``: the packed block one grid step streams, from shapes alone.

    ``k_pad`` and ``n_pad`` are the padded axes, multiples of the sub-tile
    ``(sub_k, sub_n)``; ``field_rows`` the per-block rows of each streamed
    field.  Among sub-tile multiples dividing both axes (no padding beyond
    the sub-tile's), whose :func:`_vmem_bytes` fits ``VMEM_BUDGET``: the most
    weights per step up to ``STEP_WEIGHTS``, then the fewest (more steps
    for the double buffer to overlap), then the longest K.
    """
    field_rows = tuple(field_rows)
    best, best_key = (sub_k, sub_n), None
    for bk in _divisor_multiples(k_pad, sub_k):
        for bn in _divisor_multiples(n_pad, sub_n):
            if _vmem_bytes(bm, bk, bn, w, field_rows, x_bytes, sub_k,
                           sub_n) > VMEM_BUDGET:
                continue
            work = bk * bn
            key = (min(work, STEP_WEIGHTS), -work, bk)
            if best_key is None or key > best_key:
                best, best_key = (bk, bn), key
    return best


def _fold_kernel(x_ref, *refs, decode, w, sub_k, sub_n):
    """o += x @ W over one grid step's block, in (sub_k, sub_n) sub-tiles.

    ``refs`` are the packed field blocks ``(BK/w, rows, BN)``, the scale
    block ``(1, BN)`` and the output block ``(bm, BN)``; ``decode(*fields,
    scale)`` turns one sub-tile's field slices into its scaled
    ``(sub_k, sub_n)`` f32 weights.
    """
    *field_refs, scale_ref, o_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sbnb = sub_k // w
    n_k = x_ref.shape[1] // sub_k

    def column(j, carry):
        cols = pl.ds(pl.multiple_of(j * sub_n, sub_n), sub_n)

        def k_tile(i, acc):
            blocks = pl.ds(pl.multiple_of(i * sbnb, sbnb), sbnb)
            wv = decode(*(r[blocks, :, cols] for r in field_refs),
                        scale_ref[:, cols])
            xv = x_ref[:, pl.ds(pl.multiple_of(i * sub_k, sub_k), sub_k)]
            return acc + jnp.dot(xv.astype(jnp.float32), wv,
                                 preferred_element_type=jnp.float32)

        o_ref[:, cols] = lax.fori_loop(0, n_k, k_tile, o_ref[:, cols])
        return carry

    lax.fori_loop(0, o_ref.shape[1] // sub_n, column, 0)


def _matmul_call(decode, x, fields, scale, *, w, block_m, block_n, block_k,
                 sub_k, sub_n, interpret):
    """pallas_call shared by the 2-D kernels: y(M, N) = x @ W, W streamed as
    ``fields`` ((nb, rows, N) each) and ``scale`` (1, N); every axis
    pre-padded to its block."""
    m, k_dim = x.shape
    nb, _, n = fields[0].shape
    sub_k, sub_n = sub_k or block_k, sub_n or block_n
    assert k_dim == nb * w, (k_dim, nb, w)
    assert sub_k % w == 0 and block_k % sub_k == 0 and block_n % sub_n == 0
    assert m % block_m == 0 and n % block_n == 0 and k_dim % block_k == 0
    bnb = block_k // w
    field_spec = lambda rows: pl.BlockSpec(                    # noqa: E731
        (bnb, rows, block_n), lambda i, j, kk: (kk, 0, j))
    return pl.pallas_call(
        functools.partial(_fold_kernel, decode=decode, w=w, sub_k=sub_k,
                          sub_n=sub_n),
        grid=(m // block_m, n // block_n, k_dim // block_k),
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
                  *(field_spec(f.shape[1]) for f in fields),
                  pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        compiler_params=_mosaic_params(interpret,
                                       vmem_limit_bytes=VMEM_LIMIT),
    )(x, *fields, scale)


@_scoped("strum:onehot")
def strum_matmul_pallas(x, mask, hi, lo, scale, *, w: int, n_low: int, q: int,
                        method: str, block_m: int = 128, block_n: int = 128,
                        block_k: int = 128, sub_n: int | None = None,
                        sub_k: int | None = None, interpret: bool = True):
    """y(M,N) = x(M,K) @ dequant(packed W).  All dims pre-padded to blocks.

    Operands are the PackedStruM fields:
      mask  (nb, w//8, N) uint8,  hi (nb, n_high, N) int8,
      lo    (nb, lb, N)   uint8,  scale (1, N) f32.
    A grid step streams a ``(block_k, block_n)`` block and decodes it in
    ``(sub_k, sub_n)`` sub-tiles (default: the whole block).
    """
    assert w % 8 == 0, "kernel path requires byte-aligned mask rows"
    return _matmul_call(
        functools.partial(_decode_tile, w=w, n_low=n_low, q=q, method=method),
        x, [mask, hi, lo], scale, w=w, block_m=block_m, block_n=block_n,
        block_k=block_k, sub_k=sub_k, sub_n=sub_n, interpret=interpret)


def _mosaic_params(interpret: bool, grid_rank: int = 3,
                   vmem_limit_bytes=None):
    if interpret:
        return None
    # all axes are parallel except the innermost reduction (k) axis
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1) + ("arbitrary",),
        vmem_limit_bytes=vmem_limit_bytes)


@_scoped("strum:maskfree")
def strum_matmul_pallas_maskfree(x, lo, scale, *, w: int, q: int, method: str,
                                 block_m: int = 128, block_n: int = 128,
                                 block_k: int = 128, sub_n: int | None = None,
                                 sub_k: int | None = None,
                                 interpret: bool = True):
    """y = x @ dequant(W) when n_low == w: mask and hi are never streamed."""
    assert method in ("dliq", "mip2q"), method
    return _matmul_call(
        functools.partial(_decode_tile_maskfree, w=w, q=q, method=method),
        x, [lo], scale, w=w, block_m=block_m, block_n=block_n,
        block_k=block_k, sub_k=sub_k, sub_n=sub_n, interpret=interpret)


def _decode_dense(hi_i8, scale_f32):
    """n_low = 0 decode: the hi payload is the block in order."""
    hv = hi_i8.astype(jnp.float32)                           # (bnb, w, bn)
    bnb, w, bn = hv.shape
    return hv.reshape(bnb * w, bn) * scale_f32


@_scoped("strum:dense")
def strum_matmul_pallas_dense(x, hi, scale, *, w: int,
                              block_m: int = 128, block_n: int = 128,
                              block_k: int = 128, sub_n: int | None = None,
                              sub_k: int | None = None,
                              interpret: bool = True):
    """y = x @ dequant(W) when n_low == 0: pure-INT8 blocks, no mask/lo.

    The only variant with no ``w % 8`` constraint — the hi payload carries
    all ``w`` values per block, so the mask header is never consulted.
    """
    assert hi.shape[1] == w, (hi.shape, w)
    return _matmul_call(
        _decode_dense, x, [hi], scale, w=w, block_m=block_m, block_n=block_n,
        block_k=block_k, sub_k=sub_k, sub_n=sub_n, interpret=interpret)


# ----------------------------------------------------------------- draft --
#
# Reduced-fidelity lowerings over the *same* packed payload — the draft half
# of self-speculative decoding.  Each streams a strict subset of the target
# payload's fields and never touches the rest (no pad, no load, no BlockSpec
# entry), so a traced draft step provably reads fewer HBM bytes than the
# full-fidelity step it shares buffers with:
#
# ``strum_matmul_pallas_histream``   mask + hi + scale: high values land at
#                                    their true positions, low positions
#                                    decode to zero (the sparsity decode of
#                                    an arbitrary codec).  Skips the lo
#                                    stream entirely.
# ``strum_matmul_pallas_maskfree_p`` hi + scale only: the block is treated
#                                    as all-high with the hi codes at the
#                                    leading positions — position-scrambled
#                                    and lossier, but mask- and lo-free.

@_scoped("strum:draft_histream")
def strum_matmul_pallas_histream(x, mask, hi, scale, *, w: int, n_low: int,
                                 block_m: int = 128, block_n: int = 128,
                                 block_k: int = 128, sub_n: int | None = None,
                                 sub_k: int | None = None,
                                 interpret: bool = True):
    """Draft decode: hi codes at their masked positions, lo set to zero.

    Streams mask + hi + scale — the lo payload never appears as an
    operand, so the draft step's HBM read is the Eq.-1 payload minus the
    ``ceil(n_low*q/8)`` bytes/block of the lo stream.
    """
    assert w % 8 == 0, "histream path requires byte-aligned mask rows"

    def decode(mask_u8, hi_i8, scale_f32):
        # the sparsity decode: high values placed, every low position zero
        return _decode_tile(mask_u8, hi_i8, None, scale_f32, w=w,
                            n_low=n_low, q=0, method="sparsity")
    return _matmul_call(
        decode, x, [mask, hi], scale, w=w, block_m=block_m, block_n=block_n,
        block_k=block_k, sub_k=sub_k, sub_n=sub_n, interpret=interpret)


@_scoped("strum:draft_maskfree_p")
def strum_matmul_pallas_maskfree_p(x, hi, scale, *, w: int, n_low: int,
                                   block_m: int = 128, block_n: int = 128,
                                   block_k: int = 128,
                                   sub_n: int | None = None,
                                   sub_k: int | None = None,
                                   interpret: bool = True):
    """Draft decode: hi codes at the leading block positions, rest zero.

    Streams hi + scale only — neither the mask header nor the lo payload is
    an operand.  Positions are scrambled relative to the true layout (the
    mask is what orders them), so this is the cheapest *and* lossiest
    fidelity level in the family.
    """
    n_high = w - n_low
    assert n_high >= 1, "maskfree_p draft needs at least one high value"
    assert hi.shape[1] == n_high, (hi.shape, n_high)

    def decode(hi_i8, scale_f32):
        hv = hi_i8.astype(jnp.float32)                   # (bnb, n_high, bn)
        bnb, _, bn = hv.shape
        if n_high < w:
            hv = jnp.concatenate(
                [hv, jnp.zeros((bnb, w - n_high, bn), jnp.float32)], axis=1)
        return hv.reshape(bnb * w, bn) * scale_f32
    return _matmul_call(
        decode, x, [hi], scale, w=w, block_m=block_m, block_n=block_n,
        block_k=block_k, sub_k=sub_k, sub_n=sub_n, interpret=interpret)


# --------------------------------------------------------------- grouped --
#
# Expert-stack lowerings: grid (G, M/bm, N/bn, K/bk) with the *lead* stack
# axis outermost.  Each (g, i, j, kk) step streams group g's packed payload
# tile and decodes it with the same helpers as the 2-D kernels — the MoE
# expert contraction never materializes dense weights in HBM.

def _kernel_grouped(x_ref, mask_ref, hi_ref, lo_ref, scale_ref, o_ref, *,
                    w, n_low, q, method):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    wv = _decode_tile(mask_ref[0], hi_ref[0], lo_ref[0], scale_ref[0],
                      w=w, n_low=n_low, q=q, method=method)
    x = x_ref[0].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, wv, preferred_element_type=jnp.float32)[None]


@_scoped("strum:grouped_onehot")
def strum_matmul_pallas_grouped(x, mask, hi, lo, scale, *, w: int,
                                n_low: int, q: int, method: str,
                                block_m: int = 128, block_n: int = 128,
                                block_k: int = 128, interpret: bool = True):
    """y(G,M,N) = batched x(G,M,K) @ dequant(packed W[g]) per stack group.

    Operands are stacked PackedStruM fields:
      mask  (G, nb, w//8, N) uint8,  hi (G, nb, n_high, N) int8,
      lo    (G, nb, lb, N)   uint8,  scale (G, 1, N) f32.
    """
    g, m, k_dim = x.shape
    nb, n = mask.shape[1], mask.shape[3]
    assert k_dim == nb * w, (k_dim, nb, w)
    assert w % 8 == 0, "grouped onehot path requires byte-aligned mask rows"
    assert block_k % w == 0
    assert m % block_m == 0 and n % block_n == 0 and k_dim % block_k == 0
    bnb = block_k // w
    grid = (g, m // block_m, n // block_n, k_dim // block_k)
    kern = functools.partial(_kernel_grouped, w=w, n_low=n_low, q=q,
                             method=method)
    n_high = w - n_low
    mb, lb = w // 8, lo.shape[2]
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bnb, mb, block_n),
                         lambda e, i, j, kk: (e, kk, 0, j)),
            pl.BlockSpec((1, bnb, max(n_high, 1), block_n),
                         lambda e, i, j, kk: (e, kk, 0, j)),
            pl.BlockSpec((1, bnb, max(lb, 1), block_n),
                         lambda e, i, j, kk: (e, kk, 0, j)),
            pl.BlockSpec((1, 1, block_n), lambda e, i, j, kk: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        interpret=interpret,
        compiler_params=_mosaic_params(interpret, grid_rank=4),
    )(x, mask, hi, lo, scale)


def _kernel_grouped_maskfree(x_ref, lo_ref, scale_ref, o_ref, *, w, q, method):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    wv = _decode_tile_maskfree(lo_ref[0], scale_ref[0], w=w, q=q,
                               method=method)
    x = x_ref[0].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, wv, preferred_element_type=jnp.float32)[None]


@_scoped("strum:grouped_maskfree")
def strum_matmul_pallas_grouped_maskfree(x, lo, scale, *, w: int, q: int,
                                         method: str, block_m: int = 128,
                                         block_n: int = 128,
                                         block_k: int = 128,
                                         interpret: bool = True):
    """Grouped p = 1.0 path: per-group lo payload only, no mask/hi stream."""
    g, m, k_dim = x.shape
    _, nb, lb, n = lo.shape
    assert k_dim == nb * w, (k_dim, nb, w)
    assert method in ("dliq", "mip2q"), method
    assert block_k % w == 0
    assert m % block_m == 0 and n % block_n == 0 and k_dim % block_k == 0
    bnb = block_k // w
    grid = (g, m // block_m, n // block_n, k_dim // block_k)
    kern = functools.partial(_kernel_grouped_maskfree, w=w, q=q, method=method)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bnb, lb, block_n),
                         lambda e, i, j, kk: (e, kk, 0, j)),
            pl.BlockSpec((1, 1, block_n), lambda e, i, j, kk: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        interpret=interpret,
        compiler_params=_mosaic_params(interpret, grid_rank=4),
    )(x, lo, scale)


def _kernel_grouped_dense(x_ref, hi_ref, scale_ref, o_ref, *, w):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    hv = hi_ref[0].astype(jnp.float32)                       # (bnb, w, bn)
    bnb, _, bn = hv.shape
    wv = hv.reshape(bnb * w, bn) * scale_ref[0]
    x = x_ref[0].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, wv, preferred_element_type=jnp.float32)[None]


@_scoped("strum:grouped_dense")
def strum_matmul_pallas_grouped_dense(x, hi, scale, *, w: int,
                                      block_m: int = 128, block_n: int = 128,
                                      block_k: int = 128,
                                      interpret: bool = True):
    """Grouped n_low = 0 path: pure-INT8 blocks per group, no mask/lo, any w."""
    g, m, k_dim = x.shape
    _, nb, rows, n = hi.shape
    assert rows == w and k_dim == nb * w, (rows, w, k_dim, nb)
    assert block_k % w == 0
    assert m % block_m == 0 and n % block_n == 0 and k_dim % block_k == 0
    bnb = block_k // w
    grid = (g, m // block_m, n // block_n, k_dim // block_k)
    kern = functools.partial(_kernel_grouped_dense, w=w)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bnb, w, block_n),
                         lambda e, i, j, kk: (e, kk, 0, j)),
            pl.BlockSpec((1, 1, block_n), lambda e, i, j, kk: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        interpret=interpret,
        compiler_params=_mosaic_params(interpret, grid_rank=4),
    )(x, hi, scale)
