"""Pallas TPU kernel: fused packed-decode paged attention (flash-decode).

The serving decode hot loop previously materialized the dense fp cache in
HBM (`gather_decode_pages` → `(B, S, KV, hd)` einsums), forfeiting the
paper's Eq.-1/2 bandwidth win exactly where it matters.  This kernel fuses
the whole sealed-page half of paged attention into one Pallas program:

  packed page bytes (HBM) → VMEM → StruM block decode (`_decode_blocks`,
  shared with the weight kernels) → per-page scale → QKᵀ → online softmax
  (running max + normalizer carried across the page-block grid axis) → ·V
  accumulation

so sealed KV pages are read from HBM **only as mask/hi/lo bytes** and the
decoded tiles never leave VMEM.  The hot tail page and the fresh token are
*not* handled here — callers run them as a small fp epilogue tile and merge
the two unnormalized softmax states (see ``models/attention.py``).

Outputs are the flash-attention partial state ``(acc, m, l)``:

  acc (B, KV, R, hd) f32   unnormalized sum of exp(s - m) · V
  m   (B, KV, R)     f32   running row max (NEG_INF where no valid page)
  l   (B, KV, R)     f32   running normalizer sum

``R`` is the number of query rows sharing one KV head — ``rep`` for
single-token decode, ``chunk * rep`` for chunked prefill (whose sealed
pages are causally valid for *every* chunk row, since chunks start
page-aligned).

Grid: ``(B, KV / G, ceil(P / Pc))``.  One step covers a block of ``Pc``
consecutive page-table entries of ``G`` KV heads: the payload blocks are
``(1, Pc * nb, rows, G * hd)`` and the scale block ``(1, Pc, 1, G * hd)``.
The page-block axis is innermost (``"arbitrary"`` — the online-softmax
state is a cross-block reduction carry).  ``(Pc, G)`` is a function of the
call's shapes alone (:func:`block_shape`): decode's few query rows fold
every head and several pages, chunked prefill's many rows fewer.

The page table and ``n_valid`` are scalar-prefetch operands in SMEM.  The
page-block ``index_map`` clamps the block index to the slot's last live
block, so the steps past it repeat that block index and the pipeline
issues no DMA for them; a ``pl.when`` on "block start < ``n_valid``" skips
their arithmetic.  Inside a live block, positions of a page whose table id
is ``< 0`` or whose index is ``>= n_valid`` are masked to NEG_INF and
contribute ``exp = 0``, so a block with no live page leaves the carry at
``m = NEG_INF, l = 0, acc = 0`` — no NaN.  A table whose ``P`` is not a
multiple of ``Pc`` is padded with ``-1`` entries (and zero payload).

Matches the dense attention oracle in interpret mode on CPU
(tests/test_fused_attention.py), compiles for TPU v5e at the serving cells'
decode and prefill shapes (tests/test_tpu_compile.py), and serves OLMo-1B's
decode and chunked prefill in ``chip_smoke.py`` on a real chip.
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
    _decode_blocks,
    _decode_blocks_maskfree,
    _mosaic_params,
    _scoped,
)

__all__ = [
    "strum_paged_attention_pallas",
    "strum_paged_attention_pallas_maskfree",
    "block_shape",
    "grid_plan",
    "NEG_INF",
]

NEG_INF = -1e30

# Block choice.  A grid step costs a fixed ~0.2-0.3 us on v5e whatever it
# holds, so a step aims for ``STEP_ELEMENTS`` decoded K values (a 1 MiB f32
# tile): decode work then outweighs the step cost, while a block stays
# small enough that a slot's last, partly live block decodes few dead
# positions.  ``VMEM_BUDGET`` bounds the estimate of ``_vmem_bytes``; the
# compiler is given ``VMEM_LIMIT`` (v5e has 128 MiB of VMEM per core).
STEP_ELEMENTS = 1 << 18
VMEM_BUDGET = 16 << 20
VMEM_LIMIT = 32 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _vmem_bytes(pc, g, r, hd, ps, nb, field_rows) -> int:
    """Estimated VMEM of one grid step: double-buffered blocks (8-bit rows
    pad to 32 sublanes, f32 rows to 8, a trailing 1 to 128 lanes), one
    head's decoded K and V with their temporaries, and its score tiles."""
    lanes = g * hd
    page = nb * sum(_round_up(rows, 32) for rows in field_rows) + 8 * 4
    blocks = 2 * 2 * pc * page * lanes                      # K, V payloads
    r8 = _round_up(r, 8)
    carry = 2 * g * r8 * 4 * (2 * hd + 2 * 128)             # q, acc, m, l
    decode = 12 * pc * ps * hd * 4
    scores = 3 * r8 * _round_up(pc * ps, 128) * 4
    return blocks + carry + decode + scores


def block_shape(r: int, kv: int, hd: int, pages: int, ps: int, nb: int,
                field_rows, budget: int = VMEM_BUDGET) -> tuple[int, int]:
    """``(Pc, G)``: pages and KV heads per grid step, from shapes alone.

    Among ``G`` dividing ``kv`` and power-of-two ``Pc`` up to the table's
    length, whose :func:`_vmem_bytes` fits ``budget``: the most decoded
    elements per step up to ``STEP_ELEMENTS``, then the fewest (less dead
    decode in a slot's last block), then the most heads.
    """
    field_rows = tuple(field_rows)
    pc_max = 1 << max(pages - 1, 0).bit_length()
    best, best_key = (1, 1), None
    for g in (d for d in range(1, kv + 1) if kv % d == 0):
        pc = 1
        while pc <= pc_max:
            if _vmem_bytes(pc, g, r, hd, ps, nb, field_rows) > budget:
                break
            work = pc * ps * g * hd
            key = (min(work, STEP_ELEMENTS), -work, g)
            if best_key is None or key > best_key:
                best, best_key = (pc, g), key
            pc *= 2
    return best


def grid_plan(q_shape, pages: int, ps: int, nb: int, field_rows):
    """``(Pc, G, grid)`` of a call with query rows ``q_shape`` (B, KV, R,
    hd) over a ``pages``-entry table of ``ps``-position pages."""
    b, kv, r, hd = q_shape
    pc, g = block_shape(r, kv, hd, pages, ps, nb, field_rows)
    return pc, g, (b, kv // g, -(-pages // pc))


def _fold_block(q_ref, ids_ref, nv_ref, acc_ref, m_ref, l_ref, decode_kv, *,
                pc, ps, hd, g):
    """Shared flash-decode step: init the carry on block 0, then fold one
    block of ``pc`` pages for each of the step's ``g`` heads.

    ``decode_kv(h)`` returns head ``h``'s scaled ``(pc * ps, hd)`` f32 K and
    V tiles.  ``ids_ref`` (B, P) and ``nv_ref`` (B,) are scalar-prefetch
    operands in SMEM.
    """
    b = pl.program_id(0)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    base = c * pc
    nv = nv_ref[b]

    @pl.when(base < nv)
    def _fold():
        # a position is live where its page is assigned and sealed
        pos = lax.broadcasted_iota(jnp.int32, (1, pc * ps), 1)
        ids = jnp.full((1, pc * ps), ids_ref[b, base], jnp.int32)
        for j in range(1, pc):
            ids = jnp.where(pos >= j * ps, ids_ref[b, base + j], ids)
        live = (ids >= 0) & (pos < (nv - base) * ps)

        def head(h, carry):
            kt, vt = decode_kv(h)                              # (pc*ps, hd)
            qv = q_ref[0, h]                                   # (R, hd)
            sc = lax.dot_general(qv, kt, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            sc = jnp.where(live, sc, NEG_INF)                  # (R, pc*ps)
            m_prev = m_ref[0, h]                               # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            pexp = jnp.where(live, jnp.exp(sc - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[0, h] = l_ref[0, h] * corr + jnp.sum(pexp, axis=-1,
                                                       keepdims=True)
            acc_ref[0, h] = acc_ref[0, h] * corr + jnp.dot(
                pexp, vt, preferred_element_type=jnp.float32)
            m_ref[0, h] = m_new
            return carry

        lax.fori_loop(0, g, head, 0)


def _lanes(ref, h, hd):
    """Head ``h``'s ``hd`` feature lanes of a ``(1, n, rows, G*hd)`` block."""
    return ref[0, :, :, pl.ds(pl.multiple_of(h * hd, hd), hd)]


def _scaled(vals, s_ref, h, hd, pc):
    """(pc*nb, w, hd) unscaled block values -> (pc*ps, hd), each page times
    its own per-channel scale."""
    bnb, w, bn = vals.shape
    pages = vals.reshape(pc, bnb // pc * w, bn) * _lanes(s_ref, h, hd)
    return pages.reshape(bnb * w, bn)


def _kernel(ids_ref, nv_ref, q_ref, km_ref, kh_ref, kl_ref, ks_ref, vm_ref,
            vh_ref, vl_ref, vs_ref, acc_ref, m_ref, l_ref, *, w, n_low, q,
            method, pc, ps, hd, g):
    def dec(m_ref_, h_ref, lo_ref, s_ref, h):
        vals = _decode_blocks(_lanes(m_ref_, h, hd), _lanes(h_ref, h, hd),
                              _lanes(lo_ref, h, hd), w=w, n_low=n_low, q=q,
                              method=method)
        return _scaled(vals, s_ref, h, hd, pc)

    def decode_kv(h):
        return (dec(km_ref, kh_ref, kl_ref, ks_ref, h),
                dec(vm_ref, vh_ref, vl_ref, vs_ref, h))

    _fold_block(q_ref, ids_ref, nv_ref, acc_ref, m_ref, l_ref, decode_kv,
                pc=pc, ps=ps, hd=hd, g=g)


def _kernel_maskfree(ids_ref, nv_ref, q_ref, kl_ref, ks_ref, vl_ref, vs_ref,
                     acc_ref, m_ref, l_ref, *, w, q, method, pc, ps, hd, g):
    def dec(lo_ref, s_ref, h):
        vals = _decode_blocks_maskfree(_lanes(lo_ref, h, hd), w=w, q=q,
                                       method=method)
        return _scaled(vals, s_ref, h, hd, pc)

    _fold_block(q_ref, ids_ref, nv_ref, acc_ref, m_ref, l_ref,
                lambda h: (dec(kl_ref, ks_ref, h), dec(vl_ref, vs_ref, h)),
                pc=pc, ps=ps, hd=hd, g=g)


def _call(kern, q4, payload, page_ids, n_valid, nb, w, interpret):
    """Shared pallas_call plumbing for both kernel flavors.

    q4        (B, KV, R, hd) f32, pre-scaled query rows
    payload   list of (B, P, nb, rows, F) packed fields followed by their
              (B, P, 1, F) f32 scales — already gathered per (slot, page)
    page_ids  (B, P) int32, original table entries (−1 = unassigned)
    n_valid   (B,) int32, pages strictly before this index are sealed

    ``kern`` takes the block's ``pc``, ``ps``, ``hd`` and ``g`` as keywords.
    """
    b, kv, r, hd = q4.shape
    pp = page_ids.shape[1]
    ps = nb * w
    if interpret is None:
        interpret = default_interpret()
    pc, g, grid = grid_plan(q4.shape, pp, ps, nb,
                            [a.shape[3] for a in payload if a.ndim == 5])
    pad = grid[2] * pc - pp
    if pad:
        page_ids = jnp.pad(page_ids, ((0, 0), (0, pad)), constant_values=-1)
        payload = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in payload]

    def page_map(bi, hb, c, ids_ref, nv_ref):
        # steps past the slot's last live block repeat it: no DMA
        return bi, jnp.minimum(c, jnp.maximum(nv_ref[bi] - 1, 0) // pc), 0, hb

    in_specs = [pl.BlockSpec((1, g, r, hd), lambda bi, hb, c, *_: (bi, hb, 0, 0))]
    flat = []
    for a in payload:            # fields (B, P*nb, rows, F), scales (B, P, 1, F)
        lead = pc * nb if a.ndim == 5 else pc
        a = a.reshape(b, -1, a.shape[-2], a.shape[-1])
        in_specs.append(pl.BlockSpec((1, lead, a.shape[2], g * hd), page_map))
        flat.append(a)
    out_spec = lambda cols: pl.BlockSpec(                      # noqa: E731
        (1, g, r, cols), lambda bi, hb, c, *_: (bi, hb, 0, 0))

    acc, m, l = pl.pallas_call(
        functools.partial(kern, pc=pc, ps=ps, hd=hd, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=[out_spec(hd), out_spec(1), out_spec(1)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, r, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, r, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_mosaic_params(interpret, grid_rank=3,
                                       vmem_limit_bytes=VMEM_LIMIT),
    )(page_ids, n_valid, q4, *flat)
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
      k/v_mask  (B, P, nb, w//8, F)   uint8
      k/v_hi    (B, P, nb, n_high, F) int8
      k/v_lo    (B, P, nb, lb, F)     uint8
      k/v_scale (B, P, 1, F)          f32
    with ``F = KV * hd`` matching ``q4``'s ``(B, KV, R, hd)`` layout, so KV
    head ``h`` owns feature columns ``[h*hd, (h+1)*hd)``.

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
