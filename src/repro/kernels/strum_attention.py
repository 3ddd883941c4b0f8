"""Pallas TPU kernel: fused packed-decode paged attention (flash-decode).

The serving decode hot loop previously materialized the dense fp cache in
HBM (`gather_decode_pages` → `(B, S, KV, hd)` einsums), forfeiting the
paper's Eq.-1/2 bandwidth win exactly where it matters.  This kernel fuses
the whole sealed-page half of paged attention into one Pallas program per
``(batch, kv_head, page)`` grid point:

  packed page bytes (HBM) → VMEM → StruM block decode (`_decode_tile`,
  shared with the weight kernels) → QKᵀ → online softmax (running max +
  normalizer carried across the page grid axis) → ·V accumulation

so sealed KV pages are read from HBM **only as mask/hi/lo bytes** and the
decoded ``(page_size, hd)`` tile never leaves VMEM.  The hot tail page and
the fresh token are *not* handled here — callers run them as a small fp
epilogue tile and merge the two unnormalized softmax states (see
``models/attention.py``), which keeps the kernel free of per-position
masking: a sealed page is either fully valid for every query row or not
scheduled at all.

Outputs are the flash-attention partial state ``(acc, m, l)``:

  acc (B, KV, R, hd) f32   unnormalized sum of exp(s - m) · V
  m   (B, KV, R)     f32   running row max (NEG_INF where no valid page)
  l   (B, KV, R)     f32   running normalizer sum

``R`` is the number of query rows sharing one KV head — ``rep`` for
single-token decode, ``chunk * rep`` for chunked prefill (whose sealed
pages are causally valid for *every* chunk row, since chunks start
page-aligned).

Unassigned pages (id < 0) and pages at or beyond ``n_valid`` (the hot tail
and unwritten slots) are skipped under ``pl.when``, which both masks them
to NEG_INF semantically and avoids NEG_INF − NEG_INF NaNs in the rescale.

Grid: ``(B, KV, P)`` with the page axis innermost (``"arbitrary"``
semantics — the online-softmax state is a cross-page reduction carry).
The page table and ``n_valid`` are scalar-prefetch operands: they sit in
SMEM, where the per-page ``pl.when`` branches read them.

Matches the dense attention oracle in interpret mode on CPU
(tests/test_fused_attention.py), compiles for TPU v5e at head_dim 128 /
page_size 16 (tests/test_tpu_compile.py), and serves OLMo-1B's decode and
chunked prefill in ``chip_smoke.py`` on a real chip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret
from repro.kernels.strum_matmul import (
    _decode_tile,
    _decode_tile_maskfree,
    _mosaic_params,
    _scoped,
)

__all__ = [
    "strum_paged_attention_pallas",
    "strum_paged_attention_pallas_maskfree",
    "NEG_INF",
]

NEG_INF = -1e30


def _online_update(q_ref, ids_ref, nv_ref, acc_ref, m_ref, l_ref, decode_kv):
    """Shared flash-decode step: init carry on page 0, then fold one page.

    ``decode_kv()`` returns the ``(page_size, hd)`` f32 K and V tiles; it is
    only invoked (via pl.when) for live pages, so decode work is skipped for
    unassigned (-1) ids and for pages at/after the hot tail.  ``ids_ref``
    (B, P) and ``nv_ref`` (B,) are scalar-prefetch operands in SMEM.
    """
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = (ids_ref[b, p] >= 0) & (p < nv_ref[b])

    @pl.when(live)
    def _fold():
        kt, vt = decode_kv()                                   # (ps, hd) f32
        qv = q_ref[0, 0]                                       # (R, hd)
        sc = lax.dot_general(qv, kt, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (R, ps)
        m_prev = m_ref[0, 0]                                   # (R, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        pexp = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)                         # 0 on 1st page
        l_ref[0, 0] = l_ref[0, 0] * corr + jnp.sum(pexp, axis=-1,
                                                   keepdims=True)
        acc_ref[0, 0] = acc_ref[0, 0] * corr + jnp.dot(
            pexp, vt, preferred_element_type=jnp.float32)
        m_ref[0, 0] = m_new


def _kernel(ids_ref, nv_ref, q_ref, km_ref, kh_ref, kl_ref, ks_ref, vm_ref,
            vh_ref, vl_ref, vs_ref, acc_ref, m_ref, l_ref, *, w, n_low, q,
            method):
    def decode_kv():
        kt = _decode_tile(km_ref[0, 0], kh_ref[0, 0], kl_ref[0, 0],
                          ks_ref[0, 0], w=w, n_low=n_low, q=q, method=method)
        vt = _decode_tile(vm_ref[0, 0], vh_ref[0, 0], vl_ref[0, 0],
                          vs_ref[0, 0], w=w, n_low=n_low, q=q, method=method)
        return kt, vt

    _online_update(q_ref, ids_ref, nv_ref, acc_ref, m_ref, l_ref, decode_kv)


def _kernel_maskfree(ids_ref, nv_ref, q_ref, kl_ref, ks_ref, vl_ref, vs_ref,
                     acc_ref, m_ref, l_ref, *, w, q, method):
    def dec(lo_ref, s_ref):
        return _decode_tile_maskfree(lo_ref[0, 0], s_ref[0, 0], w=w, q=q,
                                     method=method)

    _online_update(q_ref, ids_ref, nv_ref, acc_ref, m_ref, l_ref,
                   lambda: (dec(kl_ref, ks_ref), dec(vl_ref, vs_ref)))


def _call(kern, q4, payload, page_ids, n_valid, nb, w, interpret):
    """Shared pallas_call plumbing for both kernel flavors.

    q4        (B, KV, R, hd) f32, pre-scaled query rows
    payload   list of (B, P, nb, rows, hd) packed fields followed by their
              (B, P, 1, hd) f32 scales — already gathered per (slot, page)
    page_ids  (B, P) int32, original table entries (−1 = unassigned)
    n_valid   (B,) int32, pages strictly before this index are sealed

    ``page_ids`` and ``n_valid`` are scalar-prefetch operands: they land in
    SMEM ahead of the grid, where the kernel branches on them per page.
    """
    b, kv, r, hd = q4.shape
    pp = page_ids.shape[1]
    if interpret is None:
        interpret = default_interpret()

    in_specs = [pl.BlockSpec((1, 1, r, hd), lambda b, g, p, *_: (b, g, 0, 0))]
    for a in payload:
        if a.ndim == 5:
            in_specs.append(pl.BlockSpec((1, 1, nb, a.shape[3], hd),
                                         lambda b, g, p, *_: (b, p, 0, 0, g)))
        else:                                                  # scale
            in_specs.append(pl.BlockSpec((1, 1, 1, hd),
                                         lambda b, g, p, *_: (b, p, 0, g)))
    out_spec = lambda rows: pl.BlockSpec(                      # noqa: E731
        (1, 1, r, rows), lambda b, g, p, *_: (b, g, 0, 0))

    acc, m, l = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, pp),
            in_specs=in_specs,
            out_specs=[out_spec(hd), out_spec(1), out_spec(1)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, r, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, r, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_mosaic_params(interpret, grid_rank=3),
    )(page_ids, n_valid, q4, *payload)
    return acc, m[..., 0], l[..., 0]


def _pad_rows(a):
    """Degenerate payload fields (0 rows) get one zero row — same floor the
    page-decode kernel applies, so BlockSpecs stay non-empty."""
    if a.shape[-2] == 0:
        return jnp.zeros(a.shape[:-2] + (1,) + a.shape[-1:], a.dtype)
    return a


@_scoped("strum:paged_attention")
def strum_paged_attention_pallas(
        q4, k_mask, k_hi, k_lo, k_scale, v_mask, v_hi, v_lo, v_scale,
        page_ids, n_valid, *, w: int, n_low: int, q: int, method: str,
        interpret: Optional[bool] = None):
    """Sealed-page partial of paged attention over packed pools.

    Per-slot gathered PackedStruM page fields (``B`` slots × ``P`` pages):
      k/v_mask  (B, P, nb, w//8, hd*KV → hd per block) uint8
      k/v_hi    (B, P, nb, n_high, F) int8
      k/v_lo    (B, P, nb, lb, F)     uint8
      k/v_scale (B, P, 1, F)          f32
    with ``F = KV * hd`` matching ``q4``'s ``(B, KV, R, hd)`` layout, so the
    kv-head grid axis indexes feature columns ``[g*hd, (g+1)*hd)``.

    Returns ``(acc, m, l)`` — see module docstring.  ``n_valid`` is
    ``(B,)`` or ``(B, 1)`` int32.
    """
    b, kv, r, hd = q4.shape
    _, pp, nb, mb, f = k_mask.shape
    assert mb == -(-w // 8), (mb, w)
    assert w % 8 == 0, "fused attention requires byte-aligned mask rows"
    assert f == kv * hd, (f, kv, hd)
    payload = [_pad_rows(k_mask), _pad_rows(k_hi), _pad_rows(k_lo), k_scale,
               _pad_rows(v_mask), _pad_rows(v_hi), _pad_rows(v_lo), v_scale]
    kern = functools.partial(_kernel, w=w, n_low=n_low, q=q, method=method)
    return _call(kern, q4, payload, page_ids.astype(jnp.int32),
                 n_valid.reshape(b, -1)[:, 0].astype(jnp.int32),
                 nb, w, interpret)


@_scoped("strum:paged_attention_maskfree")
def strum_paged_attention_pallas_maskfree(
        q4, k_lo, k_scale, v_lo, v_scale, page_ids, n_valid, *, w: int,
        q: int, method: str, interpret: Optional[bool] = None):
    """p = 1.0 specialization: no mask/hi streams, the lo payload is the
    whole block in order (mirrors ``strum_matmul_pallas_maskfree``)."""
    assert method in ("dliq", "mip2q"), method
    b, kv, r, hd = q4.shape
    nb = k_lo.shape[2]
    assert k_lo.shape[-1] == kv * hd, (k_lo.shape, kv, hd)
    payload = [_pad_rows(k_lo), k_scale, _pad_rows(v_lo), v_scale]
    kern = functools.partial(_kernel_maskfree, w=w, q=q, method=method)
    return _call(kern, q4, payload, page_ids.astype(jnp.int32),
                 n_valid.reshape(b, -1)[:, 0].astype(jnp.int32),
                 nb, w, interpret)
