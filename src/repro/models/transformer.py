"""Decoder stack: homogeneous-period scan over layers, hybrid interleave,
train/prefill/decode forwards.

Layer plan → period: the per-layer (mixer kind, is_moe) pattern repeats with
period P = lcm(attn_every, moe_every) (P=8 for Jamba's 1:7 + MoE-every-2;
P=1 for uniform stacks).  Parameters are stacked with a leading
``n_groups = n_layers / P`` dim per period position, and the forward is a
single ``lax.scan`` over groups whose body unrolls the P positions — HLO
size stays O(P), compile time stays flat in depth, and remat wraps the
group body.

Every lane — train, the monolithic prefill/decode, and the paged serving
lanes — is the same block body (:func:`_block`, norm → mixer → residual →
FFN → residual) run by one stack runner (:func:`_stack`); a lane differs
only in its mixer adapter, the closure that runs its attention or SSM call
at one layer position and returns that position's lane output.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba2, moe
from repro.models.layers import (apply_norm, embed_def, embed_lookup,
                                 linear_def, logits, mlp, mlp_def, norm_def)
from repro.models.params import ParamDef
from repro.models.sharding import Rules, constrain

__all__ = ["period", "n_groups", "model_defs", "forward_train",
           "prefill", "decode_step", "cache_defs", "loss_fn",
           "decode_step_paged", "prefill_chunk_step", "verify_chunk_step"]


def period(cfg) -> int:
    p = 1
    if cfg.family == "hybrid" and cfg.attn_every:
        p = math.lcm(p, cfg.attn_every)
    if cfg.n_experts and cfg.moe_every > 1:
        p = math.lcm(p, cfg.moe_every)
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return p


def n_groups(cfg) -> int:
    return cfg.n_layers // period(cfg)


def _block_def(cfg, pos: int, lead) -> dict:
    kind = cfg.layer_kind(pos)
    d = {"norm1": norm_def(cfg, lead)}
    if kind == "attn":
        d["attn"] = attn_mod.attn_def(cfg, lead)
    else:
        d["ssm"] = mamba2.ssm_def(cfg, lead)
    if cfg.d_ff > 0:
        d["norm2"] = norm_def(cfg, lead)
        if cfg.layer_is_moe(pos):
            d["moe"] = moe.moe_def(cfg, lead)
        else:
            d["mlp"] = mlp_def(cfg, lead)
    return d


def model_defs(cfg) -> dict:
    p = period(cfg)
    g = n_groups(cfg)
    defs: dict = {"embed": embed_def(cfg)}
    defs["blocks"] = {f"pos{i}": _block_def(cfg, i, (g,)) for i in range(p)}
    defs["final_norm"] = norm_def(cfg)
    if not cfg.tie_embeddings:
        defs["lm_head"] = linear_def(cfg.d_model, cfg.padded_vocab,
                                     "embed_no_fsdp", "vocab")
    return defs


def _scan_groups(body, x, xs, cfg):
    """lax.scan over stacked layer groups, or a python unroll when
    cfg.scan_layers is False (used by the dry-run cost extrapolation —
    XLA's cost_analysis counts loop bodies once)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, x, xs)
    g = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(g):
        gp = jax.tree.map(lambda a, _i=i: a[_i], xs)
        x, y = body(x, gp)
        ys.append(y)
    stack = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
    return x, stack


def _remat(body, cfg):
    """Apply the configured activation-checkpoint policy to a group body.

    'full' recomputes everything (min memory, re-plays TP all-reduces in
    backward); 'dots' saves matmul outputs so the backward never re-runs the
    sharded contractions or their collectives (§Perf knob 2).
    """
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(body)


# ------------------------------------------------------------- forwards --

def _common_kw(cfg, mesh, kw):
    kw.setdefault("strum", cfg.strum)
    kw.setdefault("accum_dtype", cfg.accum_dtype)
    if mesh is not None:
        # packed leaves (cfg.strum OR a schedule-built plan) need the mesh
        # context for the sharded:* gather path; dense leaves ignore it
        kw.setdefault("tp_mesh", mesh)
    return kw


def _block(bp: dict, per, x, mixer, cfg, mesh, rules, kw):
    """One layer: norm1 → ``mixer(bp, h, *per)`` → residual → norm2 → MoE
    or MLP → residual.  ``per`` are the lane's inputs at this layer.
    Returns ``(x, aux, out)``: ``aux`` the MoE router loss (None at a
    dense FFN), ``out`` whatever the lane's mixer returned beside its
    activations."""
    h, out = mixer(bp, apply_norm(bp["norm1"], x, cfg), *per)
    x = constrain(x + h, ("batch", None, None), rules)
    aux = None
    if cfg.d_ff > 0:
        h = apply_norm(bp["norm2"], x, cfg)
        if "moe" in bp:
            h, aux = moe.moe_apply(bp["moe"], h, cfg, mesh=mesh, **kw)
        else:
            h = mlp(bp["mlp"], h, cfg, **kw)
        x = constrain(x + h, ("batch", None, None), rules)
    return x, aux, out


def _stack(params, x, cfg, mixer, xs, mesh, rules, kw, remat=False):
    """Every layer, as one scan over groups of ``period(cfg)`` blocks.

    ``xs`` are the lane's per-layer trees (group-stacked, keyed by period
    position, e.g. ``(pools, hot)``); the scan slices them with the blocks
    and position ``i`` calls ``mixer(bp, h, *(t[f"pos{i}"] for t in xs))``.
    Returns ``(x, (outs, aux))``: the mixers' outputs keyed by position and
    the per-group sum of MoE losses (None without MoE), group-stacked.
    """
    def group(x, g):
        gp, *per = g
        aux, outs = None, {}
        for i in range(period(cfg)):
            key = f"pos{i}"
            x, a, outs[key] = _block(gp[key], [t[key] for t in per], x,
                                     mixer, cfg, mesh, rules, kw)
            if a is not None:
                aux = a if aux is None else aux + a
        return x, (outs, aux)

    body = _remat(group, cfg) if remat else group
    return _scan_groups(body, x, (params["blocks"],) + tuple(xs), cfg)


def _embed(params, tokens, cfg):
    """(B, S) token ids through the embedding, or (B, S, D) embeddings from
    a modality-stub frontend (audio / vlm) as they are."""
    if tokens.ndim == 3:
        return tokens.astype(cfg.activation_dtype)
    return embed_lookup(params["embed"], tokens, cfg.activation_dtype)


def _head(params, x, cfg, last=False):
    """Final norm, then logits (of the last position only with ``last``)."""
    x = apply_norm(params["final_norm"], x, cfg)
    return logits(params.get("lm_head"), params["embed"],
                  x[:, -1:, :] if last else x)


def forward_train(params: dict, batch: dict, cfg, mesh=None,
                  rules: Optional[Rules] = None, **kw):
    """Full forward; returns (logits_f32, total_aux)."""
    kw = _common_kw(cfg, mesh, kw)
    x = _embed(params, batch.get("embeds", batch.get("tokens")), cfg)
    b, s, _ = x.shape
    x = constrain(x, ("batch", None, None), rules)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def mixer(bp, h):
        if "attn" in bp:
            return attn_mod.attention(bp["attn"], h, cfg, positions,
                                      rules=rules, **kw), None
        return mamba2.ssm_apply(bp["ssm"], h, cfg, **kw), None

    x, (_, auxs) = _stack(params, x, cfg, mixer, (), mesh, rules, kw,
                          remat=True)
    lg = constrain(_head(params, x, cfg), ("batch", None, "vocab"), rules)
    aux = jnp.zeros((), jnp.float32) if auxs is None else jnp.sum(auxs)
    return lg, aux


def loss_fn(params, batch, cfg, mesh=None, rules=None, **kw):
    lg, aux = forward_train(params, batch, cfg, mesh, rules, **kw)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + cfg.router_aux_weight * aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------- caches --

def cache_defs(cfg, batch: int, max_len: int) -> dict:
    """ParamDef tree for the per-layer decode caches (stacked by group)."""
    p = period(cfg)
    g = n_groups(cfg)
    out = {}
    for i in range(p):
        kind = cfg.layer_kind(i)
        if kind == "attn":
            shape, axes = attn_mod.init_cache_spec(cfg, batch, max_len)
            out[f"pos{i}"] = {
                "k": ParamDef((g,) + shape, ("layers",) + axes, dtype=cfg.dtype,
                              init="zeros"),
                "v": ParamDef((g,) + shape, ("layers",) + axes, dtype=cfg.dtype,
                              init="zeros"),
            }
        else:
            (cs, ca), (ss, sa) = mamba2.ssm_cache_spec(cfg, batch)
            out[f"pos{i}"] = {
                "conv": ParamDef((g,) + cs, ("layers",) + ca, dtype=cfg.dtype,
                                 init="zeros"),
                "state": ParamDef((g,) + ss, ("layers",) + sa, dtype="float32",
                                  init="zeros"),
            }
    return out


def prefill(params: dict, batch: dict, cfg, mesh=None, rules=None, **kw):
    """Forward over a prompt; returns (last-token logits, caches).

    Attention layers emit their (k, v); ssm layers their (conv tail, state).
    """
    kw = _common_kw(cfg, mesh, kw)
    x = _embed(params, batch.get("embeds", batch.get("tokens")), cfg)
    b, s, _ = x.shape
    x = constrain(x, ("batch", None, None), rules)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    act = cfg.activation_dtype
    seq = ("batch", "cache_seq", None, None)

    def mixer(bp, h):
        if "attn" in bp:
            h, (k, v) = attn_mod.attention(bp["attn"], h, cfg, positions,
                                           return_kv=True, rules=rules, **kw)
            return h, {"k": constrain(k.astype(act), seq, rules),
                       "v": constrain(v.astype(act), seq, rules)}
        h, (conv_tail, hT) = mamba2.ssm_apply(bp["ssm"], h, cfg,
                                              return_state=True, **kw)
        return h, {"conv": conv_tail.astype(act), "state": hT}

    x, (caches, _) = _stack(params, x, cfg, mixer, (), mesh, rules, kw,
                            remat=True)
    return _head(params, x, cfg, last=True), caches


def decode_step(params: dict, token: jnp.ndarray, caches: dict,
                cache_len: jnp.ndarray, cfg, mesh=None, rules=None, **kw):
    """One decode step.  token: (B, 1) int32 (or embeds (B, 1, D)).

    Returns (logits (B, 1, V), new caches).
    """
    kw = _common_kw(cfg, mesh, kw)
    x = constrain(_embed(params, token, cfg), ("batch", None, None), rules)

    def mixer(bp, h, c):
        if "attn" in bp:
            h, (nk, nv) = attn_mod.decode_attention(
                bp["attn"], h, cfg, (c["k"], c["v"]), cache_len, **kw)
            return h, {"k": nk, "v": nv}
        h, (ncv, nst) = mamba2.ssm_decode(
            bp["ssm"], h, cfg, (c["conv"], c["state"]), **kw)
        return h, {"conv": ncv, "state": nst}

    x, (new_caches, _) = _stack(params, x, cfg, mixer, (caches,), mesh,
                                rules, kw)
    return _head(params, x, cfg), new_caches


# -------------------------------------------------------- paged serving --
#
# The serving runtime's two lanes (repro.serving.scheduler) — a decode step
# over every slot and a chunked-prefill step over one slot — both read KV
# through the page table instead of slicing a monolithic cache buffer.
# ``pools`` holds sealed pages per layer position (packed via the engine's
# ``cache:*`` codecs or raw fp; see repro.serving.pages), ``hot`` the
# per-slot mutable state (attention tail pages, SSM conv/state).  Only
# ``hot`` is functionally updated here; sealing full pages into the pools
# happens between steps, on the host, through one jitted sealer.

def decode_step_paged(params: dict, token: jnp.ndarray, pools: dict,
                      hot: dict, cache_len: jnp.ndarray,
                      page_table: jnp.ndarray, active: jnp.ndarray,
                      spec, cfg, mesh=None, rules=None,
                      cache_backend=None, **kw):
    """One decode step over paged caches.  token: (B, 1) int32.

    ``active`` (B,) bool masks the hot-state updates: parked slots and
    slots mid-prefill still ride the (static-shape) batch but must not
    corrupt their tail/SSM state — the paged twin of the seed scheduler's
    "a free slot keeps decoding garbage into a parked position".
    Returns (logits (B, 1, V), new_hot).
    """
    kw = _common_kw(cfg, mesh, kw)
    x = constrain(_embed(params, token, cfg), ("batch", None, None), rules)
    a_tail = active[:, None, None, None]

    def mixer(bp, h, pool, hot_i):
        if "attn" in bp:
            h, (nkt, nvt) = attn_mod.decode_attention_paged(
                bp["attn"], h, cfg, pool,
                (hot_i["k_tail"], hot_i["v_tail"]), spec, page_table,
                cache_len, cache_backend=cache_backend, **kw)
            return h, {"k_tail": jnp.where(a_tail, nkt, hot_i["k_tail"]),
                       "v_tail": jnp.where(a_tail, nvt, hot_i["v_tail"])}
        h, (ncv, nst) = mamba2.ssm_decode(
            bp["ssm"], h, cfg, (hot_i["conv"], hot_i["state"]), **kw)
        return h, {"conv": jnp.where(active[:, None, None], ncv,
                                     hot_i["conv"]),
                   "state": jnp.where(a_tail, nst, hot_i["state"])}

    x, (new_hot, _) = _stack(params, x, cfg, mixer, (pools, hot), mesh,
                             rules, kw)
    return _head(params, x, cfg), new_hot


def prefill_chunk_step(params: dict, tokens: jnp.ndarray, pools: dict,
                       hot: dict, page_table: jnp.ndarray, slot: jnp.ndarray,
                       start: jnp.ndarray, valid_len: jnp.ndarray,
                       spec, cfg, mesh=None, rules=None,
                       cache_backend=None, **kw):
    """One fixed-shape prefill chunk for ONE slot.  tokens: (1, C) int32.

    ``slot`` / ``start`` / ``valid_len`` are traced scalars — every prompt
    of every slot runs through this single executable, which is the
    no-recompile-storm fix for the old per-prompt-length prefill.  Returns
    ``(logits (1, C, V), new_hot, chunk_kv)``: the first generated token is
    ``argmax(logits[0, valid_len - 1])`` on the final chunk, and
    ``chunk_kv`` (per attention position, the chunk's (k, v), group-
    stacked) is what the host seals into full pages.
    """
    kw = _common_kw(cfg, mesh, kw)
    ps = spec.page_size
    x = _embed(params, tokens, cfg)
    c = x.shape[1]
    # relative offset of the new tail content inside the chunk: chunk starts
    # are page-aligned, so the ragged remainder [floor(v/ps)*ps, v) is the
    # tail page; clamp keeps the slice in-bounds when the chunk is full
    # (the tail is then logically empty and masked by length anyway)
    tail_rel = jnp.clip((valid_len // ps) * ps, 0, c - ps)

    def mixer(bp, h, pool, hot_i):
        if "attn" in bp:
            h, (ck, cv) = attn_mod.prefill_attention_paged(
                bp["attn"], h, cfg, pool, spec, page_table[slot],
                start, cache_backend=cache_backend, **kw)
            ck = ck.astype(hot_i["k_tail"].dtype)
            cv = cv.astype(hot_i["v_tail"].dtype)
            nkv_, hd_ = ck.shape[2], ck.shape[3]
            tk = jax.lax.dynamic_slice(ck, (0, tail_rel, 0, 0),
                                       (1, ps, nkv_, hd_))[0]
            tv = jax.lax.dynamic_slice(cv, (0, tail_rel, 0, 0),
                                       (1, ps, nkv_, hd_))[0]
            return h, ({"k_tail": hot_i["k_tail"].at[slot].set(tk),
                        "v_tail": hot_i["v_tail"].at[slot].set(tv)},
                       {"k": ck, "v": cv})
        h, (ncv, nst) = mamba2.ssm_prefill_chunk(
            bp["ssm"], h, cfg,
            (hot_i["conv"][slot][None], hot_i["state"][slot][None]),
            valid_len, **kw)
        return h, ({"conv": hot_i["conv"].at[slot].set(
                        ncv[0].astype(hot_i["conv"].dtype)),
                    "state": hot_i["state"].at[slot].set(nst[0])}, {})

    x, (outs, _) = _stack(params, x, cfg, mixer, (pools, hot), mesh, rules,
                          kw)
    new_hot = {k: o[0] for k, o in outs.items()}
    chunk_kv = {k: o[1] for k, o in outs.items()}
    return _head(params, x, cfg), new_hot, chunk_kv


def verify_chunk_step(params: dict, tokens: jnp.ndarray, pools: dict,
                      hot: dict, page_table: jnp.ndarray, slot: jnp.ndarray,
                      start: jnp.ndarray, spec, cfg, mesh=None, rules=None,
                      cache_backend=None, **kw):
    """Score a speculative token window for ONE slot.  tokens: (1, C) int32.

    The verify lane of self-speculative decoding: ``tokens[0]`` is the
    slot's next input token and ``tokens[1:]`` the draft continuation,
    sitting at absolute positions ``start + [0, C)`` where ``start`` is the
    slot's committed length.  Full-fidelity weights, so
    ``argmax(logits[0, j])`` is bit-identical to what plain decode would
    emit after teacher-forcing the same prefix — the acceptance rule that
    keeps speculative output token-exact.  Nothing is mutated: the
    scheduler commits accepted rows of ``chunk_kv`` into the hot tails
    itself (its KV rollback).  Attention-only stacks — SSM state cannot
    roll back a rejected window.  Returns ``(logits (1, C, V), chunk_kv)``.
    """
    kw = _common_kw(cfg, mesh, kw)
    x = _embed(params, tokens, cfg)

    def mixer(bp, h, pool, hot_i):
        if "attn" not in bp:
            raise NotImplementedError(
                "speculative verify needs an attention-only stack: SSM "
                "recurrent state cannot roll back a rejected window")
        tails = (hot_i["k_tail"][slot][None], hot_i["v_tail"][slot][None])
        h, (ck, cv) = attn_mod.verify_attention_paged(
            bp["attn"], h, cfg, pool, tails, spec, page_table[slot],
            start, cache_backend=cache_backend, **kw)
        return h, {"k": ck.astype(hot_i["k_tail"].dtype),
                   "v": cv.astype(hot_i["v_tail"].dtype)}

    x, (chunk_kv, _) = _stack(params, x, cfg, mixer, (pools, hot), mesh,
                              rules, kw)
    return _head(params, x, cfg), chunk_kv
