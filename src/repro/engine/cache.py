"""The ``cache:*`` kernel-variant family: KV-cache page codecs as registry
entries.

The serving runtime stores cold KV pages in the same ``method × w × q``
compressed layout as the weights — StruM's quantizers are post-training and
retraining-free, so the identical block machinery that packs a ``(K, N)``
kernel packs a ``(page_size, F)`` cache page (blocks run along the cache
*positions* inside a page; ``F = n_kv_heads · head_dim`` channels keep their
own int8 scale per page, the per-output-channel scheme of §IV-C).

Like every other execution decision in the engine, *which decoder* runs is
a registry selection, not an if/else at the attention call site:

``cache:pallas_decode``   stream the packed page payload into VMEM and run
                          the shared one-hot decode there
                          (:func:`repro.kernels.strum_decode`) — the HBM
                          read is the Eq.-1/2 fraction of a dense page.
``cache:xla_dequant``     vmapped jnp decode (portable fallback; off-TPU
                          ``backend="auto"`` lands here).
``cache:fp_passthrough``  identity — pages stored as raw fp values.  This
                          is what ``q >= 8`` (or no codec at all) lowers
                          to: an 8-bit-payload block costs *more* than the
                          raw int8 bytes once the mask header is added, so
                          the engine refuses to pretend it compresses.

Selection uses :func:`repro.engine.registry.select_variant` with
``LeafInfo(cache=True)`` — cache codecs and matmul lowerings never compete
— and the chosen codec is recorded per cache tree in a :class:`CacheSpec`
(a static pytree node, the ``ExecSpec`` of the cache world): the scheduler
builds it once and every jitted step inherits it through the treedef, with
the usual per-call ``backend=`` override reaching the decoder.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import blocking, packing
from repro.core.policy import StruMConfig
from repro.core.quantizers import int8_symmetric, quantize_blocks
from repro.engine.registry import (LeafInfo, register_kernel, resolve_backend,
                                   get_variant, select_variant)

__all__ = ["CacheSpec", "build_cache_spec", "select_cache_variant",
           "select_attn_variant", "encode_page", "decode_pages",
           "gather_decode_pages", "attn_sealed_partial",
           "page_payload_bytes", "pages_gathered"]

CACHE_PAYLOAD_KEYS = ("mask", "hi", "lo", "scale")

NEG_INF = -1e30  # matches models.attention / kernels.strum_attention


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Static per-cache-tree codec metadata (the cache-side ``ExecSpec``).

    Registered as a static pytree node so it rides the jit treedef of the
    paged cache trees: page size, codec config, and the registry-selected
    decode variant flow through the unmodified decode step with zero traced
    leaves.
    """

    page_size: int
    cfg: Optional[StruMConfig] = None   # None = raw fp pages
    variant: str = "cache:fp_passthrough"
    backend: Optional[str] = None       # backend the variant was selected
                                        # under (None = auto)
    attn_variant: str = "cache:attn_unfused"  # fused-attention consumer of
                                        # the sealed pools (the cache:attn_*
                                        # partition) selected with the codec

    @property
    def packed(self) -> bool:
        """Do pools store payload arrays (vs raw fp pages)?"""
        return self.variant != "cache:fp_passthrough"

    @property
    def blocks_per_page(self) -> int:
        assert self.packed
        return self.page_size // self.cfg.w


try:
    jax.tree_util.register_static(CacheSpec)
except ValueError:
    pass  # already registered (module reload)


def _is_identity(cfg: Optional[StruMConfig]) -> bool:
    """Configs whose packed form would not beat raw storage: no codec, or a
    full-width (q >= 8) payload — the mask header alone makes those a net
    loss, so they lower to fp passthrough."""
    return cfg is None or (cfg.method != "sparsity" and cfg.q >= 8)


def select_cache_variant(cfg: Optional[StruMConfig], *, page_size: int,
                         feat: int, backend: Optional[str] = None):
    info = LeafInfo(k_dim=page_size, n_out=feat, cache=True)
    return select_variant(cfg, info, backend=backend)


def select_attn_variant(cfg: Optional[StruMConfig], *, page_size: int,
                        feat: int, backend: Optional[str] = None):
    """Pick the ``cache:attn_*`` consumer of the sealed pools: the fused
    flash-decode kernel where the codec supports it, the gather-then-einsum
    fallback (``cache:attn_unfused``) everywhere else."""
    info = LeafInfo(k_dim=page_size, n_out=feat, cache=True, attn=True)
    return select_variant(cfg, info, backend=backend)


def build_cache_spec(cfg: Optional[StruMConfig], *, page_size: int,
                     feat: int, backend: Optional[str] = None) -> CacheSpec:
    """Validate the (codec, page geometry) pair and select its decoder.

    ``page_size`` must be a multiple of the codec's block width ``w`` —
    pages are blocked along cache positions, and a ragged final block would
    break the uniform-page-address property the allocator relies on.
    """
    if cfg is not None and not _is_identity(cfg) and page_size % cfg.w:
        raise ValueError(f"page_size={page_size} must be a multiple of the "
                         f"cache codec's block width w={cfg.w}")
    variant = select_cache_variant(cfg, page_size=page_size, feat=feat,
                                   backend=backend)
    attn = select_attn_variant(cfg, page_size=page_size, feat=feat,
                               backend=backend)
    return CacheSpec(page_size=page_size, cfg=cfg, variant=variant.name,
                     backend=backend, attn_variant=attn.name)


# ------------------------------------------------------------- encode side --

def encode_page(page: jnp.ndarray, cfg: StruMConfig) -> dict:
    """Compress one ``(page_size, F)`` page to the Fig.-5 payload arrays.

    Traceable (runs under jit/vmap): the sealing step the scheduler invokes
    when a page fills is one compiled executable regardless of which page
    or slot it targets.
    """
    page_size, _ = page.shape
    codes, scale = int8_symmetric(page.astype(jnp.float32), axis=0)
    qb = quantize_blocks(blocking.to_blocks(codes, cfg.w), cfg.method,
                         cfg.n_low, q=cfg.q, L=cfg.L)
    p = packing.pack(qb, method=cfg.method, scale=scale, k_dim=page_size,
                     n_low=cfg.n_low, q=cfg.q, L=cfg.L)
    return {"mask": p.mask, "hi": p.hi, "lo": p.lo, "scale": p.scale}


def page_payload_bytes(page_size: int, feat: int, cfg: StruMConfig) -> int:
    """Resident packed bytes of one page (mask + hi + lo, excl. scales)."""
    nb = blocking.num_blocks(page_size, cfg.w)
    mb, nh, lb = packing.field_dims(cfg.w, cfg.n_low, cfg.q, cfg.method)
    return nb * (mb + nh + lb) * feat


# ------------------------------------------------------------- decode side --

def _pick_cache(spec: CacheSpec, backend: Optional[str]):
    """(variant, interpret flag) for this decode call — same override rule
    as :func:`repro.engine.dispatch._pick`: per-call backend wins, else the
    spec's recorded selection is authoritative."""
    if backend is None:
        _, interpret = resolve_backend(spec.backend)
        return get_variant(spec.variant), interpret
    _, interpret = resolve_backend(backend)
    return select_cache_variant(spec.cfg, page_size=spec.page_size,
                                feat=1, backend=backend), interpret


def decode_pages(leaf: dict, spec: CacheSpec, *,
                 backend: Optional[str] = None,
                 out_dtype=jnp.float32) -> jnp.ndarray:
    """Decode a batch of pages through the spec's selected ``cache:*`` codec.

    ``leaf``: packed pools hold payload arrays ``(lead..., nb, rows, F)``
    (+ ``scale (lead..., 1, F)``); passthrough pools hold
    ``{"pages": (lead..., page_size, F)}``.  Returns
    ``(lead..., page_size, F)`` in ``out_dtype``.
    """
    variant, interpret = _pick_cache(spec, backend)
    if telemetry.enabled():
        telemetry.inc(f"cache/decode/{variant.name}")
        if spec.packed:
            # packed payload bytes this decode streams out of the pools —
            # the cache-side Eq.-1 numerator (uint8/int8 fields: size==bytes)
            telemetry.inc("cache/decode_packed_bytes",
                          sum(int(leaf[k].size) for k in ("mask", "hi", "lo")
                              if k in leaf))
    # the span fires at jit-trace time (once per compiled step) — it marks
    # *that and where* a cache:* decode is part of the program; runtime
    # attribution comes from the named_scope in XLA profiles
    with telemetry.span(variant.name, cat="cache"), \
            jax.named_scope(variant.name):
        return variant.fn(leaf, cfg=spec.cfg, page_size=spec.page_size,
                          out_dtype=out_dtype, interpret=interpret)


def gather_decode_pages(pool: dict, page_ids: jnp.ndarray, spec: CacheSpec,
                        *, backend: Optional[str] = None,
                        out_dtype=jnp.float32) -> jnp.ndarray:
    """Page-table lookup: gather ``page_ids`` out of a pool and decode them.

    ``pool`` holds the pool arrays with the page axis leading (packed:
    payload fields ``(n_pages, nb, rows, F)``; passthrough:
    ``{"pages": (n_pages, page_size, F)}``).  ``page_ids`` is any-shaped
    int32; unassigned entries (< 0) are clipped to page 0 — the caller masks
    positions beyond the sequence length, so what a junk page decodes to
    never reaches the softmax.  Returns ``(*page_ids.shape, page_size, F)``.
    """
    ids = jnp.clip(page_ids, 0, None)
    keys = CACHE_PAYLOAD_KEYS if spec.packed else ("pages",)
    gathered = {k: jnp.take(pool[k], ids, axis=0) for k in keys}
    return decode_pages(gathered, spec, backend=backend, out_dtype=out_dtype)


# ------------------------------------------------------ registry entries --

@register_kernel(
    "cache:fp_passthrough", family="xla", priority=30, cache=True,
    redispatch=True,  # identity under any backend is never a substitution
    supports=lambda cfg, info: _is_identity(cfg),
    description="raw fp pages, identity decode (no codec, or q >= 8 where "
                "the packed form would cost more than the raw bytes)")
def _fp_passthrough(leaf, *, cfg, page_size, out_dtype=jnp.float32,
                    interpret=None):
    return leaf["pages"].astype(out_dtype)


@register_kernel(
    "cache:xla_dequant", family="xla", priority=0, cache=True,
    supports=lambda cfg, info: cfg is not None and not _is_identity(cfg),
    description="vmapped jnp decode of packed pages (portable fallback)")
def _xla_dequant(leaf, *, cfg, page_size, out_dtype=jnp.float32,
                 interpret=None):
    lead = leaf["mask"].shape[:-3]
    g = math.prod(lead)
    flat = {k: leaf[k].reshape((g,) + leaf[k].shape[len(lead):])
            for k in CACHE_PAYLOAD_KEYS}

    def one(mask, hi, lo, scale):
        p = packing.PackedStruM(
            method=cfg.method, w=cfg.w, n_low=cfg.n_low, q=cfg.q, L=cfg.L,
            k_dim=page_size, scale=scale, mask=mask, hi=hi, lo=lo)
        return packing.dequantize(p, jnp.float32)

    out = jax.vmap(one)(flat["mask"], flat["hi"], flat["lo"], flat["scale"])
    return out.reshape(lead + out.shape[1:]).astype(out_dtype)


@register_kernel(
    "cache:pallas_decode", family="pallas", priority=10, cache=True,
    supports=lambda cfg, info: (cfg is not None and not _is_identity(cfg)
                                and cfg.w % 8 == 0),
    description="stream packed page payloads into VMEM, one-hot decode "
                "there — HBM reads stay at the Eq.-1/2 ratio")
def _pallas_decode(leaf, *, cfg, page_size, out_dtype=jnp.float32,
                   interpret=None):
    from repro.kernels.ops import default_interpret
    from repro.kernels.strum_decode import strum_page_decode_pallas
    if interpret is None:
        interpret = default_interpret()
    lead = leaf["mask"].shape[:-3]
    g = math.prod(lead)

    def flat(k, min_rows=False):
        a = leaf[k].reshape((g,) + leaf[k].shape[len(lead):])
        if min_rows and a.shape[-2] == 0:  # BlockSpec rows must be >= 1
            a = jnp.zeros(a.shape[:-2] + (1,) + a.shape[-1:], a.dtype)
        return a

    out = strum_page_decode_pallas(
        flat("mask"), flat("hi", True), flat("lo", True), flat("scale"),
        w=cfg.w, n_low=cfg.n_low, q=cfg.q, method=cfg.method,
        interpret=interpret)
    return out.reshape(lead + out.shape[1:]).astype(out_dtype)


# ------------------------------------------- fused-attention consumers --
#
# The ``cache:attn_*`` partition (LeafInfo.attn): variants that *consume*
# the sealed pools as paged attention's sealed-page half instead of handing
# decoded pages back.  Contract:
#
#   fn(pool, qf, page_table, n_valid, *, cfg, spec, backend, interpret)
#       -> (acc, m, l)
#
#   pool        {"k": leaf, "v": leaf} pool arrays, page axis leading
#   qf          (B, KV, R, hd) f32 query rows, pre-scaled by 1/sqrt(hd)
#   page_table  (B, P) int32, -1 = unassigned
#   n_valid     (B,) int32 — pages strictly before this are sealed & valid
#
# returning the unnormalized online-softmax state over all sealed pages
# (acc (B, KV, R, hd); m, l (B, KV, R); m = NEG_INF / l = 0 where a slot
# has no sealed page yet).  The caller runs the hot tail page + fresh token
# as an fp epilogue and merges the two states — see models/attention.py.

def attn_sealed_partial(pool: dict, qf: jnp.ndarray, page_table: jnp.ndarray,
                        n_valid: jnp.ndarray, spec: CacheSpec, *,
                        backend: Optional[str] = None):
    """Sealed-page partial attention through the spec's ``cache:attn_*``
    variant (per-call ``backend`` re-selects, same rule as decode)."""
    if backend is None:
        _, interpret = resolve_backend(spec.backend)
        variant = get_variant(spec.attn_variant)
    else:
        _, interpret = resolve_backend(backend)
        variant = select_attn_variant(spec.cfg, page_size=spec.page_size,
                                      feat=1, backend=backend)
    span = variant.name.replace("cache:attn_", "attn:")
    with telemetry.span(span, cat="attn"), jax.named_scope(span):
        return variant.fn(pool, qf, page_table, n_valid, cfg=spec.cfg,
                          spec=spec, backend=backend, interpret=interpret)


@register_kernel(
    "cache:attn_unfused", family="xla", priority=0, cache=True, attn=True,
    redispatch=True,  # page decode re-selects with the caller's backend, so
                      # landing here off-TPU / for fp pools isn't a datapath
                      # substitution — the codec still runs packed
    supports=lambda cfg, info: True,
    description="gather-then-einsum fallback: decode sealed pages to dense "
                "fp (through the codec variant), then run QK^T / softmax / "
                "AV as XLA ops")
def _attn_unfused(pool, qf, page_table, n_valid, *, cfg, spec, backend=None,
                  interpret=None):
    b, kv, r, hd = qf.shape
    pp = page_table.shape[-1]
    ps = spec.page_size
    k_seq = gather_decode_pages(pool["k"], page_table, spec,
                                backend=backend).reshape(b, pp * ps, kv, hd)
    v_seq = gather_decode_pages(pool["v"], page_table, spec,
                                backend=backend).reshape(b, pp * ps, kv, hd)
    pos = jnp.arange(pp * ps, dtype=jnp.int32)
    assigned = jnp.take(page_table, pos // ps, axis=1) >= 0      # (B, S)
    valid = (pos[None, :] < (n_valid * ps)[:, None]) & assigned
    sc = jnp.einsum("bgrd,bsgd->bgrs", qf, k_seq)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1)                                     # (B,KV,R)
    pexp = jnp.where(valid[:, None, None, :],
                     jnp.exp(sc - m[..., None]), 0.0)
    l = jnp.sum(pexp, axis=-1)
    acc = jnp.einsum("bgrs,bsgd->bgrd", pexp, v_seq)
    return acc, m, l


def _gather_packed(pool: dict, page_table: jnp.ndarray, keys) -> dict:
    """Per-(slot, page) packed payload gather of every table entry, sealed
    or not (-1 reads page 0) — the fused path's HBM read of the sealed
    pools, in packed bytes only.  The kernel then fetches from this copy
    only the page blocks up to each slot's last sealed page."""
    ids = jnp.clip(page_table, 0, None)
    return {k: jnp.take(pool[k], ids, axis=0) for k in keys}


def pages_gathered(page_table_shape) -> int:
    """Pages one sealed-page attention call gathers per attention position
    for a page table of ``page_table_shape`` (n_slots, pages_per_seq):
    ``_gather_packed`` (and the unfused path's page decode) copies every
    entry of the table, sealed or not, though the fused kernel reads back
    only the blocks that hold sealed pages."""
    return math.prod(page_table_shape)


def _note_fused(gk: dict, gv: dict, qf, page_table, spec) -> None:
    """Trace-time record of a fused call: the packed bytes it gathers, and
    the kernel's grid — pages and KV heads a step, steps a call — as
    counters and as an ``attn:fused/grid`` event inside the call's span."""
    if not telemetry.enabled():
        return
    from repro.kernels.strum_attention import grid_plan
    rows = [max(gk[k].shape[3], 1) for k in gk if k != "scale"]
    pc, g, grid = grid_plan(qf.shape, page_table.shape[-1], spec.page_size,
                            gk["lo"].shape[2], rows)
    steps = math.prod(grid)
    telemetry.inc("attn/fused/packed_bytes",
                  sum(int(d[k].size) for d in (gk, gv) for k in d
                      if k != "scale"))
    telemetry.inc("attn/fused/calls")
    telemetry.inc("attn/fused/grid_steps", steps)
    telemetry.event("attn:fused/grid", cat="attn", pages_per_step=pc,
                    heads_per_step=g, grid_steps=steps)


@register_kernel(
    "cache:attn_fused", family="pallas", priority=10, cache=True, attn=True,
    supports=lambda cfg, info: (cfg is not None and not _is_identity(cfg)
                                and cfg.w % 8 == 0),
    description="flash-decode megakernel: page-gather of packed bytes -> "
                "in-VMEM StruM decode -> QK^T -> online softmax -> AV, "
                "sealed pages leave HBM only as mask/hi/lo")
def _attn_fused(pool, qf, page_table, n_valid, *, cfg, spec, backend=None,
                interpret=None):
    from repro.kernels.strum_attention import strum_paged_attention_pallas
    gk = _gather_packed(pool["k"], page_table, CACHE_PAYLOAD_KEYS)
    gv = _gather_packed(pool["v"], page_table, CACHE_PAYLOAD_KEYS)
    _note_fused(gk, gv, qf, page_table, spec)
    return strum_paged_attention_pallas(
        qf, gk["mask"], gk["hi"], gk["lo"], gk["scale"],
        gv["mask"], gv["hi"], gv["lo"], gv["scale"],
        page_table, n_valid, w=cfg.w, n_low=cfg.n_low, q=cfg.q,
        method=cfg.method, interpret=interpret)


@register_kernel(
    "cache:attn_fused_maskfree", family="pallas", priority=20, cache=True,
    attn=True,
    supports=lambda cfg, info: (cfg is not None and not _is_identity(cfg)
                                and cfg.n_low == cfg.w
                                and cfg.method in ("dliq", "mip2q")),
    description="p = 1.0 flash-decode specialization: no mask/hi streams, "
                "the lo payload is the whole block in order")
def _attn_fused_maskfree(pool, qf, page_table, n_valid, *, cfg, spec,
                         backend=None, interpret=None):
    from repro.kernels.strum_attention import (
        strum_paged_attention_pallas_maskfree)
    gk = _gather_packed(pool["k"], page_table, ("lo", "scale"))
    gv = _gather_packed(pool["v"], page_table, ("lo", "scale"))
    _note_fused(gk, gv, qf, page_table, spec)
    return strum_paged_attention_pallas_maskfree(
        qf, gk["lo"], gk["scale"], gv["lo"], gv["scale"],
        page_table, n_valid, w=cfg.w, q=cfg.q, method=cfg.method,
        interpret=interpret)
