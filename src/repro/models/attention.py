"""GQA attention: flash-style chunked causal for train/prefill, cache-based
decode.  Pure JAX (the paper's kernel-level contribution is the StruM matmul,
not attention), shaped so pjit's SPMD partitioner produces the intended
collectives:

* train/prefill: heads shard over ``model``; the kv-chunk loop keeps the
  materialized score block at (B, H, qc, kc) — flash-attention memory
  behaviour without a custom kernel.  Off-diagonal future chunks are skipped
  with ``lax.cond`` so runtime matches causal FLOPs (the dry-run
  cost_analysis conservatively counts both branches; see EXPERIMENTS.md).
* decode: the KV cache shards its *sequence* dim over ``model``
  (flash-decode): QKᵀ is local, softmax / AV reduce over the sharded axis
  as small collectives — no cache gather.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, linear, linear_def

__all__ = ["attn_def", "attention", "decode_attention", "init_cache_spec",
           "decode_attention_paged", "prefill_attention_paged",
           "verify_attention_paged"]

NEG_INF = -1e30


def attn_def(cfg, lead=()) -> dict:
    d, hd = cfg.d_model, cfg.hd
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": linear_def(d, nh * hd, "embed", "qkv", bias=cfg.qkv_bias, lead=lead),
        "wk": linear_def(d, nkv * hd, "embed", "qkv", bias=cfg.qkv_bias, lead=lead),
        "wv": linear_def(d, nkv * hd, "embed", "qkv", bias=cfg.qkv_bias, lead=lead),
        "wo": linear_def(nh * hd, d, "qkv", "embed", lead=lead),
    }


def _qkv(p, x, cfg, positions, **kw):
    b, s, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kw_c = dict(kw, tp_pattern="col")
    q = linear(p["wq"], x, **kw_c).reshape(b, s, nh, hd)
    k = linear(p["wk"], x, **kw_c).reshape(b, s, nkv, hd)
    v = linear(p["wv"], x, **kw_c).reshape(b, s, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _chunked_causal(q, k, v, chunk: int):
    """Online-softmax blocked causal attention.

    q: (B, S, H, D), k/v: (B, S, KV, D).  Returns (B, S, H, D) f32.
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    qc = kc = min(chunk, s)
    pad = (-s) % qc
    s_real = s
    if pad:  # ragged tail: padded keys sit at future positions (masked out
        # by causality for every real query); padded query rows are sliced.
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nq, nk = s // qc, s // kc
    scale = 1.0 / math.sqrt(d)

    qf = (q.astype(jnp.float32) * scale).reshape(b, nq, qc, kv, rep, d)
    kf = k.astype(jnp.float32).reshape(b, nk, kc, kv, d)
    vf = v.astype(jnp.float32).reshape(b, nk, kc, kv, d)
    q_pos = jnp.arange(s).reshape(nq, qc)
    k_pos = jnp.arange(s).reshape(nk, kc)

    def q_block(qi, q_i):
        # q_i: (B, qc, KV, rep, D)
        def kv_step(carry, inp):
            m, l, acc = carry
            kj, k_j, v_j, kp = inp

            def do(_):
                sc = jnp.einsum("bqgrd,bkgd->bgrqk", q_i, k_j)
                mask = q_pos[qi][:, None] >= kp[None, :]
                sc = jnp.where(mask[None, None, None], sc, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
                p = jnp.exp(sc - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bgrqk,bkgd->bgrqd", p, v_j)
                return m_new, l_new, acc_new

            return jax.lax.cond(kj <= qi, do, lambda _: carry, None), None

        m0 = jnp.full((b, kv, rep, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, rep, qc), jnp.float32)
        a0 = jnp.zeros((b, kv, rep, qc, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), kf.swapaxes(0, 1), vf.swapaxes(0, 1), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, d)

    outs = jax.lax.map(lambda args: q_block(args[0], args[1]),
                       (jnp.arange(nq), qf.swapaxes(0, 1)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)
    return out[:, :s_real]


def attention(p: dict, x: jnp.ndarray, cfg, positions: jnp.ndarray,
              return_kv: bool = False, rules=None, **kw):
    """Training / prefill attention.  x: (B, S, D)."""
    from repro.models.sharding import constrain
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions, **kw)
    if cfg.attn_heads_constraint and rules is not None:
        # pin head sharding so the q-chunk loop's dynamic slices don't make
        # SPMD fall back to involuntary full resharding (§Perf knob)
        q = constrain(q, ("batch", None, "heads", None), rules)
        k = constrain(k, ("batch", None, "kv_heads", None), rules)
        v = constrain(v, ("batch", None, "kv_heads", None), rules)
    o = _chunked_causal(q, k, v, cfg.attn_chunk).astype(x.dtype)
    y = linear(p["wo"], o.reshape(b, s, -1), **dict(kw, tp_pattern="row"))
    if return_kv:
        return y, (k, v)
    return y


def decode_attention(p: dict, x: jnp.ndarray, cfg, cache: tuple,
                     cache_len: jnp.ndarray, **kw):
    """Single-token decode.  x: (B, 1, D); cache k/v: (B, Smax, KV, hd).

    The new token attends over ``cache[:cache_len]`` plus itself; the cache
    is functionally updated at position ``cache_len``.
    """
    b, _, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rep = nh // nkv
    ck, cv = cache
    smax = ck.shape[1]
    per_slot = jnp.ndim(cache_len) == 1   # (B,) lengths: batched serving
    positions = (cache_len[:, None] if per_slot
                 else jnp.broadcast_to(cache_len, (b, 1))).astype(jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions, **kw)

    # functional cache update at each row's cache_len
    if per_slot:
        rows = jnp.arange(b)
        ck = ck.at[rows, cache_len].set(k[:, 0].astype(ck.dtype))
        cv = cv.at[rows, cache_len].set(v[:, 0].astype(cv.dtype))
        len_b = cache_len[:, None, None, None]
    else:
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, cache_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, cache_len, 0, 0))
        len_b = cache_len

    qf = (q.astype(jnp.float32) / math.sqrt(hd)).reshape(b, nkv, rep, hd)
    sc = jnp.einsum("bgrd,bsgd->bgrs", qf, ck.astype(jnp.float32))
    valid = jnp.arange(smax)[None, None, None, :] <= len_b
    sc = jnp.where(valid, sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", w, cv.astype(jnp.float32))
    o = o.reshape(b, 1, nh * hd).astype(x.dtype)
    y = linear(p["wo"], o, **dict(kw, tp_pattern="row"))
    return y, (ck, cv)


def init_cache_spec(cfg, batch: int, max_len: int):
    """ShapeDtypeStructs + logical axes for one attention layer's KV cache."""
    shape = (batch, max_len, cfg.n_kv_heads, cfg.hd)
    axes = ("batch", "cache_seq", "kv_heads", "head_dim")
    return shape, axes


# ----------------------------------------------------------- paged caches --
#
# The serving runtime stores KV in fixed-size pages (repro.serving.pages):
# sealed pages live in per-layer pools — packed via the engine's ``cache:*``
# codecs or as raw fp — and each slot keeps one hot tail page it is writing.
#
# Paged attention splits into two partials merged by their online-softmax
# states (flash-attention algebra — the merged result is bit-for-bit the
# same softmax, just associatively regrouped):
#
#   sealed half   every fully-sealed page, computed through the engine's
#                 ``cache:attn_*`` variant (repro.engine.cache): the fused
#                 flash-decode Pallas kernel reads packed bytes only; the
#                 unfused fallback gathers + decodes + einsums.  A sealed
#                 page is either valid for *every* query row or skipped
#                 (ids < 0 and pages at/after the tail mask to NEG_INF),
#                 so junk pages and retired requests never reach softmax.
#   fp epilogue   the hot tail page + fresh token (decode) or the chunk
#                 itself (prefill) — fp values that never lived in a pool.

def _merge_partials(parts):
    """Merge unnormalized online-softmax states [(acc, m, l), ...].

    acc (..., R, hd), m/l (..., R).  Empty partials (m = NEG_INF, l = 0)
    contribute nothing: at least one part is always non-empty (the epilogue
    contains the fresh token / the chunk diagonal), so ``m_tot`` is finite
    and the empty part's correction factor underflows to exactly 0.
    """
    m_tot = parts[0][1]
    for _, m, _ in parts[1:]:
        m_tot = jnp.maximum(m_tot, m)
    acc_tot = jnp.zeros_like(parts[0][0])
    l_tot = jnp.zeros_like(parts[0][2])
    for acc, m, l in parts:
        c = jnp.exp(m - m_tot)
        acc_tot = acc_tot + acc * c[..., None]
        l_tot = l_tot + l * c
    return acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]


def _fp_partial(q, k, v, valid):
    """Masked online-softmax partial over fp keys/values (no pool).

    q (B, KV, R, hd) f32, pre-scaled; k/v (B, P, KV, hd); ``valid``
    broadcasts to the scores (B, KV, R, P).  Returns the unnormalized
    ``(acc, m, l)`` of :func:`_merge_partials`.  A row with no valid key
    scores exp(0) everywhere, so the select zeroes it: (0, NEG_INF, 0)
    then merges as nothing.
    """
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    sc = jnp.where(valid, jnp.einsum("bgrd,bpgd->bgrp", q, k), NEG_INF)
    m = jnp.max(sc, axis=-1)
    pexp = jnp.where(valid, jnp.exp(sc - m[..., None]), 0.0)
    l = jnp.sum(pexp, axis=-1)
    return jnp.einsum("bgrp,bpgd->bgrd", pexp, v), m, l


def decode_attention_paged(p: dict, x: jnp.ndarray, cfg, pool: dict,
                           tails: tuple, spec, page_table: jnp.ndarray,
                           cache_len: jnp.ndarray, cache_backend=None, **kw):
    """Single-token decode over a paged (possibly packed) KV cache.

    x: (B, 1, D); ``pool`` is this layer's page pool (page axis leading —
    the layer scan already sliced the group dim); ``tails`` the slot-hot
    ``(k_tail, v_tail)`` of shape (B, page_size, KV, hd); ``page_table``
    (B, pages_per_seq) int32 page ids (-1 = unassigned); ``cache_len`` (B,).

    The sealed pages (indices < ``cache_len // page_size``) run through the
    registry-selected ``cache:attn_*`` partial; the hot tail page — with the
    fresh token appended at ``cache_len % page_size`` — is an fp epilogue
    tile, and the two online-softmax states merge exactly.

    Functionally updates only the tails; sealing a full tail into the pool
    is the scheduler's job, between steps.  Returns
    ``(y, (new_k_tail, new_v_tail))``.
    """
    from repro.engine.cache import attn_sealed_partial
    b = x.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rep = nh // nkv
    kt, vt = tails
    ps = spec.page_size
    positions = cache_len[:, None].astype(jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions, **kw)

    # append the fresh token into the hot tail
    rows = jnp.arange(b)
    new_kt = kt.at[rows, cache_len % ps].set(k[:, 0].astype(kt.dtype))
    new_vt = vt.at[rows, cache_len % ps].set(v[:, 0].astype(vt.dtype))

    qf = (q.astype(jnp.float32) / math.sqrt(hd)).reshape(b, nkv, rep, hd)
    n_valid = (cache_len // ps).astype(jnp.int32)
    sealed = attn_sealed_partial(pool, qf, page_table, n_valid, spec,
                                 backend=cache_backend)

    # fp epilogue: the hot tail page (fresh token included); tail index i
    # holds absolute position n_valid * ps + i
    t_pos = (n_valid * ps)[:, None] + jnp.arange(ps)[None, :]   # (B, ps)
    valid_t = (t_pos <= cache_len[:, None])[:, None, None, :]
    tail = _fp_partial(qf, new_kt, new_vt, valid_t)

    o = _merge_partials([sealed, tail])                         # (B,KV,R,hd)
    o = o.reshape(b, 1, nh * hd).astype(x.dtype)
    y = linear(p["wo"], o, **dict(kw, tp_pattern="row"))
    return y, (new_kt, new_vt)


def _chunk_attention(p, x, cfg, pool, tails, spec, table_row, start,
                     cache_backend=None, **kw):
    """Attention of ONE slot's (1, C) rows at positions ``start + [0, C)``:
    the sealed pages' partial, then (with ``tails``) the hot tail's
    committed rows, then the chunk against itself, intra-chunk causal.
    Returns ``(y, (k, v))``."""
    from repro.engine.cache import attn_sealed_partial
    b, c, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rep = nh // nkv
    ps = spec.page_size
    positions = (start + jnp.arange(c, dtype=jnp.int32))[None, :]
    positions = jnp.broadcast_to(positions, (b, c))
    q, k, v = _qkv(p, x, cfg, positions, **kw)

    qf5 = (q.astype(jnp.float32) / math.sqrt(hd)).reshape(b, c, nkv, rep, hd)
    # kernel R axis = (chunk row, rep) flattened: row i <-> (i // rep, i % rep)
    qr = qf5.transpose(0, 2, 1, 3, 4).reshape(b, nkv, c * rep, hd)
    n_valid = jnp.broadcast_to(start // ps, (b,)).astype(jnp.int32)
    parts = [attn_sealed_partial(pool, qr, table_row[None, :], n_valid, spec,
                                 backend=cache_backend)]
    if tails is not None:
        # tail index i holds absolute position (start // ps) * ps + i; rows
        # at/after ``start`` may be stale draft KV and must not score
        t_pos = (start // ps) * ps + jnp.arange(ps)
        parts.append(_fp_partial(qr, *tails,
                                 (t_pos < start)[None, None, None, :]))
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]   # (cq, ck)
    causal = jnp.broadcast_to(causal[:, None], (c, rep, c)).reshape(-1, c)
    parts.append(_fp_partial(qr, k, v, causal[None, None]))

    o = _merge_partials(parts)                          # (b, KV, c*rep, hd)
    o = o.reshape(b, nkv, c, rep, hd).transpose(0, 2, 1, 3, 4)
    o = o.reshape(b, c, nh * hd).astype(x.dtype)
    y = linear(p["wo"], o, **dict(kw, tp_pattern="row"))
    return y, (k, v)


def prefill_attention_paged(p: dict, x: jnp.ndarray, cfg, pool: dict,
                            spec, table_row: jnp.ndarray,
                            start: jnp.ndarray, cache_backend=None, **kw):
    """Chunked-prefill attention for ONE request.  x: (1, C, D).

    The chunk's tokens sit at absolute positions ``start + [0, C)``; all
    earlier content is in sealed pages (chunk starts are page-aligned, so
    there is never a partially-hot prefix) — which makes every sealed page
    causally valid for *every* chunk row, so the same ``cache:attn_*``
    partial serves prefill with the chunk's query rows flattened into the
    kernel's R axis.  The chunk itself (intra-chunk causal) is the fp
    epilogue; padded rows of a ragged final chunk land at positions beyond
    the prompt, which every valid query masks causally.  Returns
    ``(y, (k, v))`` with k/v (1, C, KV, hd) — writing them into pages/tail
    is the caller's job.
    """
    return _chunk_attention(p, x, cfg, pool, None, spec, table_row, start,
                            cache_backend, **kw)


def verify_attention_paged(p: dict, x: jnp.ndarray, cfg, pool: dict,
                           tails: tuple, spec, table_row: jnp.ndarray,
                           start: jnp.ndarray, cache_backend=None, **kw):
    """Speculation-verify attention for ONE slot.  x: (1, C, D).

    Like :func:`prefill_attention_paged`, but ``start`` (the slot's
    committed length) is NOT page-aligned: the committed prefix splits
    into ``start // ps`` sealed pages plus a partially-filled hot tail
    ``tails`` (1, ps, KV, hd), so the merge takes THREE online-softmax
    partials — sealed pages, the tail's committed rows (valid *strictly*
    below ``start``; empty when ``start`` is page-aligned, an exact no-op
    of the merge), and the intra-chunk causal block.  Every sealed page and
    committed tail row precedes every query, so only the chunk partial
    needs a causal mask.  Nothing is mutated — the caller commits accepted
    rows of the returned ``(k, v)`` itself.
    """
    return _chunk_attention(p, x, cfg, pool, tails, spec, table_row, start,
                            cache_backend, **kw)
