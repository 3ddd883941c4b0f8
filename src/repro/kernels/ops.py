"""jit'd public wrappers around the StruM Pallas kernels.

Handles tile-size selection, padding to tile multiples, payload-axis
minimum sizes, and output dtype — callers just hand in activations and a
:class:`~repro.core.packing.PackedStruM`.

``interpret`` defaults to True off-TPU (the CPU tests run the kernels in
interpret mode); on a TPU backend the same code path lowers through
Mosaic.  Set ``STRUM_INTERPRET=1`` (or ``0``) to force it either way, or
override per call — the engine API (:mod:`repro.engine`) exposes this as
``backend="interpret"``.

``variant`` selects the Pallas lowering: ``"onehot"`` (general), ``"maskfree"``
(p = 1.0, no mask/hi stream) or ``"dense"`` (n_low = 0, no mask/lo stream).
Callers normally do not pick these by hand — :mod:`repro.engine.registry`
selects the variant from each leaf's :class:`StruMConfig`.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.core.packing import PackedStruM
from repro.kernels.strum_matmul import (SUB_K, SUB_M, SUB_N, block_shape,
                                        strum_matmul_pallas,
                                        strum_matmul_pallas_dense,
                                        strum_matmul_pallas_grouped,
                                        strum_matmul_pallas_grouped_dense,
                                        strum_matmul_pallas_grouped_maskfree,
                                        strum_matmul_pallas_histream,
                                        strum_matmul_pallas_maskfree,
                                        strum_matmul_pallas_maskfree_p)

__all__ = ["strum_matmul", "strum_gemv", "strum_grouped_matmul",
           "grid_plan", "grid_steps",
           "strum_matmul_draft", "strum_gemv_draft", "draft_field_set",
           "default_interpret", "PALLAS_VARIANTS", "DRAFT_MODES"]

PALLAS_VARIANTS = ("onehot", "maskfree", "dense")

#: reduced-fidelity draft lowerings over the same payload; each streams a
#: strict subset of the packed fields (see ``draft_field_set``)
DRAFT_MODES = ("histream", "maskfree_p")


def default_interpret() -> bool:
    """Run Pallas in interpret mode?  ``STRUM_INTERPRET`` env var wins
    (``1``/``true`` forces interpret even on TPU, ``0``/``false`` forces
    compiled lowering), else interpret everywhere except a real TPU."""
    env = os.environ.get("STRUM_INTERPRET", "").strip()
    if env:  # empty/unset falls through to the backend check
        return env.lower() not in ("0", "false")
    return jax.default_backend() != "tpu"


def _pad_axis(a: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    pad = (-a.shape[axis]) % to
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _min1(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Payload axes must be >= 1 for BlockSpec; the zero filler is inert."""
    if a.shape[axis] != 0:
        return a
    shape = list(a.shape)
    shape[axis] = 1
    return jnp.zeros(tuple(shape), a.dtype)


def _validate_variant(variant: str, packed: PackedStruM) -> None:
    """Preconditions shared by the 2-D and grouped variant dispatch."""
    w = packed.w
    if variant == "onehot":
        if w % 8:
            raise ValueError(f"onehot variant needs byte-aligned mask rows "
                             f"(w={w}); use the dequant fallback")
    elif variant == "maskfree":
        if packed.n_low != w or packed.method not in ("dliq", "mip2q"):
            raise ValueError(f"maskfree variant needs n_low == w and a lo "
                             f"payload, got n_low={packed.n_low} w={w} "
                             f"method={packed.method}")
    elif variant == "dense":
        if packed.n_low != 0:
            raise ValueError(f"dense variant needs n_low == 0, "
                             f"got {packed.n_low}")
    else:
        raise ValueError(f"unknown variant {variant!r}; "
                         f"want one of {PALLAS_VARIANTS}")


def _pick_block(dim: int, pref: int, align: int) -> int:
    """Largest multiple of ``align`` that is <= ``pref``, clamped to the
    padded axis (``dim`` rounded up to ``align``) and floored at ``align``.

    The result always divides the axis after it is padded to a block
    multiple — a tiny dim (e.g. a 3x5 weight) yields exactly one
    ``align``-sized block rather than an unaligned or oversized tile.
    """
    padded = -(-dim // align) * align
    return max(align, min((pref // align) * align, padded))


#: the packed fields each 2-D lowering streams
_VARIANT_FIELDS = {"onehot": ("mask", "hi", "lo"), "maskfree": ("lo",),
                   "dense": ("hi",)}


def _field_rows(variant: str, packed: PackedStruM) -> tuple:
    """Per-block rows of each field ``variant`` (a 2-D lowering or a draft
    mode) streams; empty fields get the one zero row :func:`_min1` gives
    them."""
    fields = _VARIANT_FIELDS.get(variant) or draft_field_set(variant)
    return tuple(max(getattr(packed, f).shape[1], 1) for f in fields)


def grid_plan(m: int, k_pad: int, n: int, w: int, field_rows, *,
              x_bytes: int = 2):
    """Tiling of a 2-D packed call: ``(bm, sub_k, sub_n, BK, BN, grid)``.

    ``(bm, sub_k, sub_n)`` is the sub-tile one decode and dot cover
    (``SUB_M x SUB_K x SUB_N`` clamped to the axes by :func:`_pick_block`);
    every axis is padded to it, and
    :func:`~repro.kernels.strum_matmul.block_shape` groups sub-tiles into
    the ``(BK, BN)`` block a grid step streams.
    """
    bm = _pick_block(m, SUB_M, 8)
    sn = _pick_block(n, SUB_N, 128)
    sk = _pick_block(k_pad, SUB_K, w)
    m_pad, n_pad, k_pad = (-(-d // b) * b for d, b in
                           ((m, bm), (n, sn), (k_pad, sk)))
    bk, bn = block_shape(bm, k_pad, n_pad, w, field_rows, sub_k=sk,
                         sub_n=sn, x_bytes=x_bytes)
    return bm, sk, sn, bk, bn, (m_pad // bm, n_pad // bn, k_pad // bk)


def grid_steps(m: int, packed: PackedStruM, variant: str = "onehot",
               x_bytes: int = 2) -> int:
    """Grid steps of one call of ``variant`` (a 2-D lowering of
    :func:`strum_matmul` or a draft mode) over ``m`` rows of ``packed``."""
    *_, grid = grid_plan(m, packed.mask.shape[0] * packed.w, packed.n_out,
                         packed.w, _field_rows(variant, packed),
                         x_bytes=x_bytes)
    return math.prod(grid)


def _prepare(x: jnp.ndarray, packed: PackedStruM, variant: str):
    """Flatten leading dims, pad every operand to sub-tile multiples.

    Returns ``(x2, mask, hi, lo, scale, dims)`` where ``dims`` carries the
    tiling of :func:`grid_plan` and the unpadded (m, n) for the final slice.
    """
    lead = x.shape[:-1]
    k_in = x.shape[-1]
    if k_in != packed.k_dim:
        raise ValueError(f"x K={k_in} vs packed k_dim={packed.k_dim}")
    x2 = x.reshape(-1, k_in)
    m, n = x2.shape[0], packed.n_out
    w = packed.w

    k_pad = packed.mask.shape[0] * w               # padded K (block multiple)
    x2 = _pad_axis(x2, 1, k_pad) if k_pad != k_in else x2

    bm, sk, sn, bk, bn, _ = grid_plan(
        m, k_pad, n, w, _field_rows(variant, packed),
        x_bytes=x2.dtype.itemsize)

    x2 = _pad_axis(_pad_axis(x2, 0, bm), 1, sk)

    mask = _pad_axis(_pad_axis(packed.mask, 0, sk // w), 2, sn)
    hi = _pad_axis(_pad_axis(_min1(packed.hi, 1), 0, sk // w), 2, sn)
    lo = _pad_axis(_pad_axis(_min1(packed.lo, 1), 0, sk // w), 2, sn)
    # zero scale in padded columns kills any junk the decoder would produce
    scale = _pad_axis(packed.scale, 1, sn)
    return x2, mask, hi, lo, scale, (lead, m, n, bm, sk, sn, bk, bn)


def strum_matmul(x: jnp.ndarray, packed: PackedStruM, *,
                 out_dtype=None, interpret: bool | None = None,
                 variant: str = "onehot") -> jnp.ndarray:
    """y = x @ dequant(packed), streaming compressed weights.

    x: (..., K) — leading dims are flattened into M.
    Returns (..., N) in ``out_dtype`` (default: x.dtype).
    Each grid step streams the block :func:`grid_plan` chooses from the
    shapes and decodes it in sub-tiles.
    """
    if interpret is None:
        interpret = default_interpret()
    out_dtype = out_dtype or x.dtype
    _validate_variant(variant, packed)
    x2, mask, hi, lo, scale, (lead, m, n, bm, sk, sn, bk, bn) = _prepare(
        x, packed, variant)
    w = packed.w
    tiles = dict(block_m=bm, block_n=bn, block_k=bk, sub_n=sn, sub_k=sk,
                 interpret=interpret)

    if variant == "onehot":
        y = strum_matmul_pallas(
            x2, mask, hi, lo, scale,
            w=w, n_low=packed.n_low, q=packed.q, method=packed.method,
            **tiles)
    elif variant == "maskfree":
        y = strum_matmul_pallas_maskfree(
            x2, lo, scale, w=w, q=packed.q, method=packed.method, **tiles)
    else:
        y = strum_matmul_pallas_dense(x2, hi, scale, w=w, **tiles)
    return y[:m, :n].reshape(lead + (n,)).astype(out_dtype)


def draft_field_set(mode: str) -> tuple:
    """The packed payload fields a draft mode streams (the rest are never
    touched — not even padded — so they stay dead in the traced jaxpr)."""
    if mode == "histream":
        return ("mask", "hi")
    if mode == "maskfree_p":
        return ("hi",)
    raise ValueError(f"unknown draft mode {mode!r}; want one of {DRAFT_MODES}")


def strum_matmul_draft(x: jnp.ndarray, packed: PackedStruM, *, mode: str,
                       out_dtype=None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Reduced-fidelity y = x @ draft_dequant(packed), same payload buffers.

    The deliberately separate prepare path touches *only* the fields the
    draft mode streams: skipped streams (lo; also mask for
    ``maskfree_p``) never enter the traced program, which is what the
    ``verify_draft_payload`` analysis pass proves statically.
    """
    if interpret is None:
        interpret = default_interpret()
    out_dtype = out_dtype or x.dtype
    if mode not in DRAFT_MODES:
        raise ValueError(f"unknown draft mode {mode!r}; "
                         f"want one of {DRAFT_MODES}")
    if packed.n_low >= packed.w:
        raise ValueError(f"draft modes need high values to stream "
                         f"(n_low={packed.n_low} w={packed.w})")

    lead = x.shape[:-1]
    k_in = x.shape[-1]
    if k_in != packed.k_dim:
        raise ValueError(f"x K={k_in} vs packed k_dim={packed.k_dim}")
    x2 = x.reshape(-1, k_in)
    m, n = x2.shape[0], packed.n_out
    w = packed.w

    k_pad = packed.hi.shape[0] * w                 # padded K (block multiple)
    x2 = _pad_axis(x2, 1, k_pad) if k_pad != k_in else x2
    bm, sk, sn, bk, bn, _ = grid_plan(m, k_pad, n, w,
                                      _field_rows(mode, packed),
                                      x_bytes=x2.dtype.itemsize)
    x2 = _pad_axis(_pad_axis(x2, 0, bm), 1, sk)

    hi = _pad_axis(_pad_axis(packed.hi, 0, sk // w), 2, sn)
    scale = _pad_axis(packed.scale, 1, sn)
    tiles = dict(block_m=bm, block_n=bn, block_k=bk, sub_n=sn, sub_k=sk,
                 interpret=interpret)
    if mode == "histream":
        if w % 8:
            raise ValueError(f"histream draft needs byte-aligned mask rows "
                             f"(w={w})")
        mask = _pad_axis(_pad_axis(packed.mask, 0, sk // w), 2, sn)
        y = strum_matmul_pallas_histream(
            x2, mask, hi, scale, w=w, n_low=packed.n_low, **tiles)
    else:
        y = strum_matmul_pallas_maskfree_p(
            x2, hi, scale, w=w, n_low=packed.n_low, **tiles)
    return y[:m, :n].reshape(lead + (n,)).astype(out_dtype)


def strum_gemv_draft(x: jnp.ndarray, packed: PackedStruM, *, mode: str,
                     out_dtype=None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Decode-path draft matvec: the fidelity knob where it pays — the op is
    HBM-bound, so the skipped streams' bytes convert 1:1 into latency."""
    return strum_matmul_draft(x, packed, mode=mode, out_dtype=out_dtype,
                              interpret=interpret)


def strum_grouped_matmul(x: jnp.ndarray, packed: PackedStruM, *,
                         out_dtype=None, block_m: int = 128,
                         block_n: int = 256, block_k: int = 256,
                         interpret: bool | None = None,
                         variant: str = "onehot") -> jnp.ndarray:
    """Batched y[..., m, n] = x[..., m, :] @ dequant(W[...]) for stacked leaves.

    ``packed`` carries lead stack dims on every payload field — mask
    ``(lead..., nb, w//8, N)``, hi/lo alike, scale ``(lead..., 1, N)`` — the
    serving layout :func:`repro.models.quantize._pack_leaf` emits for MoE
    expert stacks.  ``x`` is ``(lead..., M, K)`` with ``K == packed.k_dim``
    (the true, unpadded reduction dim).  Lead dims are flattened into one
    grid axis; per-stack padding / tile selection mirrors
    :func:`strum_matmul`.  Returns ``(lead..., M, N)`` in ``out_dtype``.
    """
    if interpret is None:
        interpret = default_interpret()
    out_dtype = out_dtype or x.dtype
    _validate_variant(variant, packed)
    lead_dims = packed.mask.ndim - 3
    if lead_dims < 1:
        raise ValueError("strum_grouped_matmul needs stacked payloads "
                         "(lead dims); use strum_matmul for 2-D leaves")
    lead = packed.mask.shape[:lead_dims]
    if x.ndim != lead_dims + 2 or x.shape[:lead_dims] != lead:
        raise ValueError(f"x shape {x.shape} does not match packed lead "
                         f"dims {lead} + (M, K)")
    k_in = x.shape[-1]
    if k_in != packed.k_dim:
        raise ValueError(f"x K={k_in} vs packed k_dim={packed.k_dim}")
    w = packed.w
    m, n = x.shape[-2], packed.n_out
    nb = packed.mask.shape[-3]
    k_pad = nb * w

    bm = _pick_block(m, block_m, 8)
    bn = _pick_block(n, block_n, 128)
    bk = _pick_block(k_pad, block_k, w)

    g = math.prod(lead)
    x3 = x.reshape((g, m, k_in))
    # zero-padded x rows null out whatever the decoder produces for padded
    # K blocks (MIP2Q code 0 decodes to ±1, not 0 — junk rows are benign
    # only because the matching activations are zero)
    x3 = _pad_axis(_pad_axis(x3, 1, bm), 2, bk)

    def _flat(a):
        return a.reshape((g,) + a.shape[lead_dims:])

    mask = _pad_axis(_pad_axis(_flat(packed.mask), 1, bk // w), 3, bn)
    hi = _pad_axis(_pad_axis(_min1(_flat(packed.hi), 2), 1, bk // w), 3, bn)
    lo = _pad_axis(_pad_axis(_min1(_flat(packed.lo), 2), 1, bk // w), 3, bn)
    # zero scale in padded columns kills any junk the decoder would produce
    scale = _pad_axis(_flat(packed.scale), 2, bn)

    if variant == "onehot":
        y = strum_matmul_pallas_grouped(
            x3, mask, hi, lo, scale,
            w=w, n_low=packed.n_low, q=packed.q, method=packed.method,
            block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    elif variant == "maskfree":
        y = strum_matmul_pallas_grouped_maskfree(
            x3, lo, scale, w=w, q=packed.q, method=packed.method,
            block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    else:
        y = strum_matmul_pallas_grouped_dense(
            x3, hi, scale, w=w,
            block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return y[:, :m, :n].reshape(lead + (m, n)).astype(out_dtype)


def strum_gemv(x: jnp.ndarray, packed: PackedStruM, *, out_dtype=None,
               interpret: bool | None = None,
               variant: str = "onehot") -> jnp.ndarray:
    """Decode-path matvec: tiny M (a few tokens), full weight stream.

    This is where StruM's bandwidth ratio converts 1:1 into decode latency —
    the op is HBM-bound, so bytes saved = time saved (DESIGN.md §2).
    """
    return strum_matmul(x, packed, out_dtype=out_dtype, interpret=interpret,
                        variant=variant)
