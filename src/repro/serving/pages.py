"""Paged KV-cache storage: page allocator, pooled page arrays, codec stats.

The serving runtime replaces the monolithic ``(g, B, max_len, ...)`` cache
trees with a page table:

* every attention layer position owns a **page pool** — ``n_pages`` pages of
  ``page_size`` cache positions each, stored either packed (the Fig.-5
  ``method × w × q`` payload via :mod:`repro.engine.cache`) or as raw fp
  pages;
* one **page table** ``(n_slots, pages_per_seq)`` of page ids is shared by
  every layer (page id ``j`` addresses the ``j``-th pool slot of *all*
  pools — the classic single-table simplification);
* each slot keeps one **hot tail** page per layer — the page currently
  being written.  When it fills, the scheduler *seals* it: the tail is
  block-quantized and scattered into the pool at a freshly allocated id,
  and decode-time reads stream the packed bytes (the paper's Eq.-1/2 HBM
  ratio applied to the cache, not just the weights);
* SSM layer positions have no sequence dim to page — their O(1) recurrent
  state is a single per-slot hot page (conv tail + state), managed by the
  same hot tree.

Everything here is host-side bookkeeping plus pool-array constructors; the
device-side codec lives in :mod:`repro.engine.cache` and the paged forward
in :mod:`repro.models.attention` / :mod:`repro.models.transformer`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.engine.cache import (CacheSpec, build_cache_spec, encode_page,
                                page_payload_bytes)

__all__ = ["PagesExhausted", "PageAllocator", "pages_per_seq",
           "attn_feat_dim", "make_cache_spec", "init_pools", "init_hot",
           "make_sealer", "cache_stats"]


class PagesExhausted(RuntimeError):
    """Raised by :meth:`PageAllocator.alloc` when the pool is empty."""


class PageAllocator:
    """Free-list page allocator (host-side).

    Pages are fungible — uniform size, uniform codec — so allocation is a
    sorted free list: lowest ids first for pool locality.  ``defrag()`` is
    the retirement-time compaction hook: it re-sorts the free list and
    reports fragmentation (number of non-contiguous free runs), which is
    what a production allocator would use to pick migration candidates.
    """

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"n_pages={n_pages} must be >= 1")
        self.n_pages = n_pages
        self._free = list(range(n_pages))

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> list:
        if n > len(self._free):
            telemetry.inc("pages/alloc_fail")
            raise PagesExhausted(
                f"requested {n} pages, {len(self._free)}/{self.n_pages} free")
        out, self._free = self._free[:n], self._free[n:]
        if telemetry.enabled():
            telemetry.inc("pages/alloc", n)
            telemetry.event("page_alloc", cat="pages", n=n)
            telemetry.gauge("pages/in_use", self.n_pages - len(self._free))
        return out

    def free(self, ids) -> None:
        dup = set(ids) & set(self._free)
        if dup:
            raise ValueError(f"double free of pages {sorted(dup)}")
        self._free.extend(int(i) for i in ids)
        if telemetry.enabled():
            telemetry.inc("pages/freed", len(ids))
            telemetry.event("page_free", cat="pages", n=len(ids))
            telemetry.gauge("pages/in_use", self.n_pages - len(self._free))
        self.defrag()

    def defrag(self) -> dict:
        self._free.sort()
        runs = sum(1 for a, b in zip(self._free, self._free[1:])
                   if b != a + 1) + (1 if self._free else 0)
        if telemetry.enabled():
            telemetry.inc("pages/defrag")
            telemetry.gauge("pages/free_runs", runs)
        return {"free": len(self._free), "n_pages": self.n_pages,
                "free_runs": runs}


# --------------------------------------------------------------- geometry --

def pages_per_seq(max_len: int, page_size: int) -> int:
    """Pages needed to cover ``max_len`` positions (last page may be
    partial — ``max_len % page_size != 0`` is supported)."""
    return -(-max_len // page_size)


def attn_feat_dim(cfg) -> int:
    return cfg.n_kv_heads * cfg.hd


def make_cache_spec(cfg, kv_cache, page_size: int,
                    backend: Optional[str] = None) -> CacheSpec:
    """(model cfg, codec request) -> validated :class:`CacheSpec`.

    ``kv_cache``: ``None`` / ``"fp"`` for raw pages, or a
    :class:`StruMConfig` for packed pages.
    """
    codec = None if kv_cache in (None, "fp") else kv_cache
    return build_cache_spec(codec, page_size=page_size,
                            feat=attn_feat_dim(cfg), backend=backend)


# ---------------------------------------------------------------- storage --

def init_pools(cfg, n_pages: int, spec: CacheSpec) -> dict:
    """Page pools per layer position (attention only; SSM positions get an
    empty dict — their state is hot-only)."""
    from repro.core import packing
    from repro.models import transformer as tfm
    g = tfm.n_groups(cfg)
    f = attn_feat_dim(cfg)
    ps = spec.page_size
    out = {}
    for i in range(tfm.period(cfg)):
        if cfg.layer_kind(i) != "attn":
            out[f"pos{i}"] = {}
            continue
        if spec.packed:
            c = spec.cfg
            nb = ps // c.w
            mb, nh, lb = packing.field_dims(c.w, c.n_low, c.q, c.method)
            leaf = lambda: {  # noqa: E731
                "mask": jnp.zeros((g, n_pages, nb, mb, f), jnp.uint8),
                "hi": jnp.zeros((g, n_pages, nb, nh, f), jnp.int8),
                "lo": jnp.zeros((g, n_pages, nb, lb, f), jnp.uint8),
                "scale": jnp.zeros((g, n_pages, 1, f), jnp.float32),
            }
        else:
            leaf = lambda: {  # noqa: E731
                "pages": jnp.zeros((g, n_pages, ps, f), cfg.dtype)}
        out[f"pos{i}"] = {"k": leaf(), "v": leaf()}
    return out


def init_hot(cfg, n_slots: int, page_size: int) -> dict:
    """Per-slot hot state: the filling tail page (attention) or the O(1)
    recurrent state (SSM) — dtypes match the monolithic ``cache_defs``."""
    from repro.models import mamba2
    from repro.models import transformer as tfm
    g = tfm.n_groups(cfg)
    out = {}
    for i in range(tfm.period(cfg)):
        if cfg.layer_kind(i) == "attn":
            shape = (g, n_slots, page_size, cfg.n_kv_heads, cfg.hd)
            out[f"pos{i}"] = {"k_tail": jnp.zeros(shape, cfg.dtype),
                              "v_tail": jnp.zeros(shape, cfg.dtype)}
        else:
            (cs, _), (ss, _) = mamba2.ssm_cache_spec(cfg, n_slots)
            out[f"pos{i}"] = {
                "conv": jnp.zeros((g,) + cs, cfg.dtype),
                "state": jnp.zeros((g,) + ss, jnp.float32)}
    return out


# ---------------------------------------------------------------- sealing --

def make_sealer(spec: CacheSpec):
    """One jitted executable that seals one full page into a pool, in place.

    ``seal(pool_pos, k_src, v_src, at)``: the sources are
    ``(g, B, T, kv, hd)`` — a hot tail tree, a prefill chunk's KV window or a
    serial prefill's cache — and ``at`` is the int32 triple
    ``(row, start, page_id)``: the page is ``src[:, row, start:start + ps]``
    and lands at pool page ``page_id``.  All three are traced, so sealing any
    page of any slot reuses the compilation of its source shape.

    The pool is donated: every pool leaf aliases its output, so a seal
    writes one page instead of copying the pool.  The caller must rebind
    its pool to the result; the arrays it passed in are deleted.
    """
    ps = spec.page_size

    def _page(src, row, start):          # -> (g, ps, kv * hd)
        g, _, _, kv, hd = src.shape
        page = jax.lax.dynamic_slice(src, (0, row, start, 0, 0),
                                     (g, 1, ps, kv, hd))
        return page.reshape(g, ps, kv * hd)

    def seal(pool, k_src, v_src, at):
        row, start, page_id = at[0], at[1], at[2]
        out = dict(pool)
        for name, src in (("k", k_src), ("v", v_src)):
            flat = _page(src, row, start)
            if spec.packed:
                new = jax.vmap(lambda p: encode_page(p, spec.cfg))(
                    flat.astype(jnp.float32))
            else:
                new = {"pages": flat}
            out[name] = {k: jax.lax.dynamic_update_index_in_dim(
                pool[name][k], v.astype(pool[name][k].dtype), page_id, 1)
                for k, v in new.items()}
        return out
    return jax.jit(seal, donate_argnums=0)


# ------------------------------------------------------------------ stats --

def _tree_bytes(tree, keys=None) -> int:
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = str(getattr(path[-1], "key", ""))
        if keys is not None and name not in keys:
            continue
        total += leaf.size * leaf.dtype.itemsize
    return total


def cache_stats(pools: dict, hot: dict, spec: CacheSpec, cfg,
                n_slots: int, max_len: int) -> dict:
    """Measured resident cache bytes vs the codec's Eq.-1/2 expectation.

    The cache-side analog of :func:`repro.telemetry.all_gather_stats`: counts
    the bytes that are actually allocated, and derives the ratio against
    the same pages stored int8 (the paper's baseline) and against the
    monolithic fp cache tree the paged layout replaced.  For a packed
    codec, ``packed_page_bytes / int8_page_bytes == cfg.compression_ratio``
    exactly whenever the payload is byte-aligned (the paper's [1,16]
    p∈{.25,.5,.75} q=4 points) — tests and ``serving_bench`` assert it.
    """
    from repro.models import transformer as tfm
    g = tfm.n_groups(cfg)
    f = attn_feat_dim(cfg)
    ps = spec.page_size
    n_attn = sum(1 for i in range(tfm.period(cfg))
                 if cfg.layer_kind(i) == "attn")
    n_pages = 0
    for pos in pools.values():
        if pos:
            n_pages = pos["k"][next(iter(pos["k"]))].shape[1]
            break
    # payload bytes, measured from the arrays that exist
    if spec.packed:
        packed = sum(_tree_bytes(pos, keys=("mask", "hi", "lo"))
                     for pos in pools.values())
        scale = sum(_tree_bytes(pos, keys=("scale",))
                    for pos in pools.values())
        expected = 2 * g * n_attn * n_pages * page_payload_bytes(ps, f,
                                                                 spec.cfg)
    else:
        packed = sum(_tree_bytes(pos, keys=("pages",))
                     for pos in pools.values())
        scale = 0
        expected = packed
    int8_pages = 2 * g * n_attn * n_pages * ps * f          # same pages, int8
    dtype_bytes = jnp.dtype(cfg.dtype).itemsize
    dense = 2 * g * n_attn * n_slots * max_len * f * dtype_bytes
    if telemetry.enabled():
        # packed-vs-fp residency: what the pools hold compressed vs what
        # stays full-width (the hot tails + fp pools)
        telemetry.gauge("cache/resident_packed_bytes",
                        int(packed) if spec.packed else 0)
        telemetry.gauge("cache/resident_fp_bytes",
                        int(_tree_bytes(hot))
                        + (0 if spec.packed else int(packed)))
        telemetry.gauge("cache/ratio_vs_int8", packed / max(int8_pages, 1))
    return {
        "codec": spec.variant,
        "page_size": ps,
        "n_pages": n_pages,
        "resident_page_bytes": int(packed),
        "expected_page_bytes": int(expected),
        "scale_bytes": int(scale),
        "hot_bytes": int(_tree_bytes(hot)),
        "int8_page_bytes": int(int8_pages),
        "ratio_vs_int8": packed / max(int8_pages, 1),
        "expected_ratio_vs_int8": (spec.cfg.compression_ratio
                                   if spec.packed else float(dtype_bytes)),
        "dense_cache_bytes": int(dense),
        "ratio_vs_dense": packed / max(dense, 1),
    }
