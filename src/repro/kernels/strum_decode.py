"""Pallas TPU kernel: batched *decode-only* pass over packed StruM pages.

The serving runtime stores cold KV-cache pages in the Fig.-5 compressed
layout (mask header + mixed payload, one ``[1, w]`` block per ``w`` cache
positions of each feature channel).  Decode-time attention gathers a
request's pages and needs them back as values — there is no matmul to fuse
into (the contraction happens in the attention einsum, against activations
that only exist after rope), so this kernel is the pure decompression half
of :mod:`repro.kernels.strum_matmul`: stream the packed page payload
HBM → VMEM, run the shared one-hot scatter decode, write the value tile.

HBM economics are the same as the weight kernels': the *resident* cache and
the stream into VMEM are at the paper's Eq.-1/2 ratio; only the decoded
tile (bounded by the block shape) ever exists at full width.

Grid: ``(P, F/block_f)`` — one program per (page, feature-tile).  Block
shapes are static (StruM fixes ``n_low`` per block), so page pools are
uniformly addressable with plain block indices — the paper's "slowest-PE
balance" property, transplanted to page tables: any page can be decoded by
any program with the same DMA descriptor.

Matches the jnp packing decoder in interpret mode on CPU
(tests/test_paged_cache.py); compiled through Mosaic it decodes the packed
KV pages of ``chip_smoke.py`` on a TPU v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import default_interpret
from repro.kernels.strum_matmul import _decode_tile, _mosaic_params, _scoped

__all__ = ["strum_page_decode_pallas"]


def _kernel(mask_ref, hi_ref, lo_ref, scale_ref, o_ref, *, w, n_low, q,
            method):
    wv = _decode_tile(mask_ref[0], hi_ref[0], lo_ref[0], scale_ref[0],
                      w=w, n_low=n_low, q=q, method=method)
    o_ref[...] = wv[None]


@_scoped("strum:page_decode")
def strum_page_decode_pallas(mask, hi, lo, scale, *, w: int, n_low: int,
                             q: int, method: str, block_f: int = 512,
                             interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode P packed pages to dense values.

    Operands are per-page PackedStruM fields with a leading page axis:
      mask  (P, nb, w//8, F) uint8,  hi (P, nb, n_high, F) int8,
      lo    (P, nb, lb, F)   uint8,  scale (P, 1, F) f32.
    Returns (P, nb*w, F) f32 — ``nb*w`` is the page size (cache positions),
    ``F`` the per-token feature dim (e.g. ``n_kv_heads * head_dim``).

    ``interpret=None`` (the default) defers to the engine-wide
    ``default_interpret()`` / ``STRUM_INTERPRET`` convention, like the
    matmul kernels — real-TPU runs compile instead of silently interpreting.
    """
    if interpret is None:
        interpret = default_interpret()
    p_pages, nb, mb, f = mask.shape
    assert mb == -(-w // 8), (mb, w)
    assert w % 8 == 0, "page decode requires byte-aligned mask rows"
    n_high = hi.shape[2]
    lb = lo.shape[2]

    # pad F to the lane tile; zero scale in padded columns kills any junk
    bf = max(128, min((block_f // 128) * 128, -(-f // 128) * 128))
    pad = (-f) % bf
    if pad:
        widths = lambda a: [(0, 0)] * (a.ndim - 1) + [(0, pad)]  # noqa: E731
        mask = jnp.pad(mask, widths(mask))
        hi = jnp.pad(hi, widths(hi))
        lo = jnp.pad(lo, widths(lo))
        scale = jnp.pad(scale, widths(scale))
    fp = f + pad

    grid = (p_pages, fp // bf)
    kern = functools.partial(_kernel, w=w, n_low=n_low, q=q, method=method)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nb, mb, bf), lambda p, j: (p, 0, 0, j)),
            pl.BlockSpec((1, nb, max(n_high, 1), bf), lambda p, j: (p, 0, 0, j)),
            pl.BlockSpec((1, nb, max(lb, 1), bf), lambda p, j: (p, 0, 0, j)),
            pl.BlockSpec((1, 1, bf), lambda p, j: (p, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, nb * w, bf), lambda p, j: (p, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p_pages, nb * w, fp), jnp.float32),
        interpret=interpret,
        compiler_params=_mosaic_params(interpret, grid_rank=2),
    )(mask, hi, lo, scale)
    return out[:, :, :f]
