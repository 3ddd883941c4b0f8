"""Runtime dispatch: packed leaf (+ recorded variant) -> kernel call.

The single funnel every quantized matmul in ``models/``, ``serving/`` and
``launch/`` goes through.  A leaf built by :func:`repro.engine.build_plan`
carries an :class:`ExecSpec` (static pytree node) naming its selected
variant; legacy hand-built leaves (``{"mask", "hi", "lo", "scale"}`` plus an
explicit ``strum`` config) get a variant selected on the fly from the same
registry — there is exactly one selection rule either way.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import packing
from repro.core.policy import StruMConfig
from repro.engine.registry import (ExecSpec, LeafInfo, get_variant,
                                   resolve_backend, select_variant)

__all__ = ["dispatch", "dispatch_grouped", "apply", "dequant_leaf",
           "leaf_spec"]

PAYLOAD_KEYS = ("mask", "hi", "lo", "scale")


def _draft_mode_of(variant_name: str) -> str:
    """'draft:histream' / 'draft:xla_histream' -> 'histream'."""
    tail = variant_name.split(":", 1)[-1]
    return tail[4:] if tail.startswith("xla_") else tail


def _note_dispatch(variant, wleaf: dict, *, sharded: bool = False,
                   packed: Optional[packing.PackedStruM] = None,
                   x: Optional[jnp.ndarray] = None) -> None:
    """Count one dispatch through ``variant`` into the active recorders.

    Dispatch runs at jit-trace time, so per-executable these counters fire
    exactly once per leaf — a full forward traced from a plan yields counts
    equal to the plan's ``variant_distribution``.  ``dispatch/packed_bytes``
    is the mask+hi+lo payload (the Eq.-1 numerator; uint8/int8 fields, so
    ``size`` is bytes); for ``sharded:*`` calls the same payload is what
    the FSDP gather moves, mirrored under a dedicated counter (the runtime
    twin of :func:`repro.telemetry.all_gather_stats`).  ``draft:*`` calls
    stream only their mode's field subset, counted as such and mirrored
    under ``spec/draft_packed_bytes`` (the speculative draft lane's weight
    read).  ``dispatch/grid_steps`` counts the grid steps of each call
    over ``x`` (M, K) rows of ``packed`` whose variant registered
    ``grid_steps`` (the 2-D Pallas and draft lowerings).
    """
    if not telemetry.enabled():
        return
    telemetry.inc(f"dispatch/variant/{variant.name}")
    if getattr(variant, "draft", False):
        from repro.kernels.ops import draft_field_set
        fields = draft_field_set(_draft_mode_of(variant.name))
        payload = sum(int(wleaf[k].size) for k in fields if k in wleaf)
        telemetry.inc("spec/draft_packed_bytes", payload)
    else:
        payload = sum(int(wleaf[k].size) for k in ("mask", "hi", "lo")
                      if k in wleaf)
    telemetry.inc("dispatch/packed_bytes", payload)
    if variant.grid_steps is not None and x is not None:
        telemetry.inc("dispatch/grid_steps", variant.grid_steps(
            x.shape[0], packed, x_bytes=x.dtype.itemsize))
    if sharded:
        telemetry.inc("dispatch/sharded/gathered_packed_bytes", payload)


def leaf_spec(wleaf: dict, strum: Optional[StruMConfig] = None
              ) -> tuple[StruMConfig, Optional[ExecSpec]]:
    """Resolve the (config, spec) of a packed leaf.

    Plan-built leaves carry ``spec``; legacy leaves carry ``cfg`` (schedule
    metadata) or rely on the caller's uniform ``strum`` config.
    """
    spec = wleaf.get("spec")
    if spec is not None:
        return spec.cfg, spec
    cfg = wleaf.get("cfg", strum)
    if cfg is None:
        raise ValueError("compressed leaf needs an embedded spec/cfg or an "
                         "explicit strum config")
    return cfg, None


def _as_packed(wleaf: dict, cfg: StruMConfig, k_dim: int) -> packing.PackedStruM:
    return packing.PackedStruM(
        method=cfg.method, w=cfg.w, n_low=cfg.n_low, q=cfg.q, L=cfg.L,
        k_dim=k_dim, scale=wleaf["scale"], mask=wleaf["mask"],
        hi=wleaf["hi"], lo=wleaf["lo"])


def _check_k(spec: Optional[ExecSpec], k_dim: int) -> None:
    """A plan-built leaf records its true reduction dim — a mismatched x
    would otherwise contract against a silently truncated/padded weight."""
    if spec is not None and spec.k_dim is not None and spec.k_dim != k_dim:
        raise ValueError(f"x K={k_dim} does not match the leaf's recorded "
                         f"reduction dim K={spec.k_dim}")


def _sharded_call(wleaf: dict, x: jnp.ndarray, cfg: StruMConfig,
                  spec: Optional[ExecSpec], info: LeafInfo, *, mesh,
                  pattern: Optional[str], backend: Optional[str],
                  accum_dtype, out_dtype) -> jnp.ndarray:
    """Select + invoke a ``sharded:*`` variant (the 11-kwarg convention).

    The one implementation behind both the 2-D mesh branch of
    :func:`dispatch` and the ``fsdp_axes`` branch of
    :func:`dispatch_grouped` — the sharded fn contract changes in exactly
    one place.
    """
    variant, interpret = _pick(cfg, info, spec, backend)
    _note_dispatch(variant, wleaf, sharded=True)
    eff_backend = backend if backend is not None else (
        spec.backend if spec is not None else None)
    with jax.named_scope(variant.name):
        return variant.fn(
            wleaf, x, cfg=cfg, mesh=mesh, fsdp=tuple(info.fsdp),
            pattern=pattern, k_dim=x.shape[-1], backend=eff_backend,
            interpret=interpret, accum_dtype=accum_dtype,
            out_dtype=out_dtype)


def _pick(cfg: StruMConfig, info: LeafInfo, spec: Optional[ExecSpec],
          backend: Optional[str]):
    """(variant, interpret-flag) for this call.

    A per-call ``backend`` overrides the plan's recorded selection; without
    one, the spec's variant is authoritative (that is the point of a plan).
    A recorded variant whose sharded-ness disagrees with the *call's* mesh
    context (``info.fsdp``) is re-selected: a mesh-aware plan still serves
    single-device, and a mesh-less plan still serves distributed.
    """
    if backend is None and spec is not None:
        _, interpret = resolve_backend(spec.backend)
        variant = get_variant(spec.variant)
        if variant.sharded == bool(info.fsdp):
            return variant, interpret
        backend = spec.backend
    if spec is not None and not getattr(info, "draft", ""):
        # a per-call backend override must not silently promote a draft
        # leaf to full fidelity: re-select inside the same draft partition
        try:
            if get_variant(spec.variant).draft:
                info = info._replace(draft=_draft_mode_of(spec.variant))
        except KeyError:
            pass
    _, interpret = resolve_backend(backend)
    return select_variant(cfg, info, backend=backend), interpret


def dispatch(wleaf: dict, x: jnp.ndarray, *,
             strum: Optional[StruMConfig] = None,
             backend: Optional[str] = None,
             accum_dtype=jnp.float32, out_dtype=None,
             mesh=None, tp_mesh=None,
             tp_pattern: Optional[str] = None) -> jnp.ndarray:
    """y = x @ dequant(leaf) through the leaf's selected kernel variant.

    ``x``: (..., K); returns (..., N) in ``out_dtype`` (default x.dtype).
    Stacked leaves (lead dims, e.g. MoE expert stacks) delegate to
    :func:`dispatch_grouped` — ``x`` must then carry matching lead dims.

    With ``mesh`` (``tp_mesh`` is the legacy alias the model forwards
    thread) the leaf executes through the registry's ``sharded:*`` family:
    the FSDP all-gather moves the *packed* payloads and the per-call
    ``backend=`` still reaches the post-gather kernel.  The TP layout comes
    from ``tp_pattern`` or, for mesh-aware plan leaves, from the recorded
    ``spec.shard``.
    """
    cfg, spec = leaf_spec(wleaf, strum)
    k_dim = x.shape[-1]
    _check_k(spec, k_dim)
    out_dtype = out_dtype or x.dtype
    mesh = mesh if mesh is not None else tp_mesh
    shard = getattr(spec, "shard", None)
    pattern = tp_pattern or (shard.tp_pattern if shard is not None else None)

    lead_dims = wleaf["mask"].ndim - 3          # stacked (expert/scan) leaves
    if lead_dims > 0:
        if mesh is not None:
            # stack collectives run by axis name inside an already-entered
            # shard_map body (models.moe) — a mesh object here cannot be
            # honored, and silently going local would all-gather the
            # DEQUANTIZED stack, the regression sharded:* exists to prevent
            raise ValueError(
                "stacked (expert) leaves take the distributed path inside "
                "a shard_map body: use models.moe.moe_apply(..., mesh=...) "
                "or dispatch_grouped(..., fsdp_axes=...) from within the "
                "body, not dispatch(mesh=...)")
        return dispatch_grouped(wleaf, x, strum=strum, backend=backend,
                                accum_dtype=accum_dtype, out_dtype=out_dtype)

    if mesh is not None:
        if pattern is None:
            # silently going local would let XLA hoist the dequant above
            # the FSDP gather and move DEQUANTIZED bytes over ICI — the
            # regression the sharded:* family exists to prevent
            raise ValueError(
                "dispatch(mesh=...) on a 2-D leaf needs a TP layout: pass "
                "tp_pattern='col'|'row', or build the plan mesh-aware "
                "(build_plan(..., mesh=...)) so the leaf's spec records it")
        from repro.models.sharding import fsdp_axes as _fsdp_axes
        fsdp = (shard.fsdp_axes if shard is not None and shard.fsdp_axes
                else _fsdp_axes(mesh))
        if fsdp:  # a mesh with no FSDP axis (TP-only) serves the local path
            info = LeafInfo(k_dim=k_dim, n_out=wleaf["scale"].shape[-1],
                            fsdp=tuple(fsdp), tp_pattern=pattern)
            return _sharded_call(wleaf, x, cfg, spec, info, mesh=mesh,
                                 pattern=pattern, backend=backend,
                                 accum_dtype=accum_dtype,
                                 out_dtype=out_dtype)

    info = LeafInfo(k_dim=k_dim, n_out=wleaf["scale"].shape[-1],
                    lead=(), name="")
    variant, interpret = _pick(cfg, info, spec, backend)
    packed = _as_packed(wleaf, cfg, k_dim)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k_dim)
    _note_dispatch(variant, wleaf, packed=packed, x=x2)
    with jax.named_scope(variant.name):
        y = variant.fn(x2, packed, out_dtype=out_dtype,
                       interpret=interpret, accum_dtype=accum_dtype)
    return y.reshape(lead + (y.shape[-1],))


def dispatch_grouped(wleaf: dict, x: jnp.ndarray, *,
                     strum: Optional[StruMConfig] = None,
                     backend: Optional[str] = None,
                     accum_dtype=jnp.float32,
                     out_dtype=None, fsdp_axes=None) -> jnp.ndarray:
    """Batched y[..., c, n] = x[..., c, :] @ dequant(leaf[...]) for stacks.

    ``x``: (lead..., C, K) where ``lead`` matches the leaf's stack dims —
    e.g. MoE expert buffers ``(E, C, D)`` against a packed ``(E, D, F)``
    stack.  Selection goes through the same registry as 2-D dispatch: a
    ``grouped`` variant (``pallas:grouped*``) streams the compressed stack
    through a lead-axis Pallas grid; any non-grouped selection (the
    ``xla:dequant`` fallback) decompresses the stack at its *true* K and
    contracts with a batched XLA dot.

    ``fsdp_axes`` marks a call from inside an already-entered shard_map
    body whose payload block axis is still FSDP-sharded over those mesh
    axes (the MoE expert path): selection then goes to the ``sharded:*``
    family — ``sharded:grouped_gather`` all-gathers the *packed* stack and
    re-dispatches here on the gathered form with the same ``backend``.
    """
    cfg, spec = leaf_spec(wleaf, strum)
    lead_dims = wleaf["mask"].ndim - 3
    if lead_dims == 0:
        return dispatch(wleaf, x, strum=strum, backend=backend,
                        accum_dtype=accum_dtype, out_dtype=out_dtype)
    lead = wleaf["mask"].shape[:lead_dims]
    if x.ndim != lead_dims + 2 or tuple(x.shape[:lead_dims]) != tuple(lead):
        raise ValueError(
            f"stacked leaf with lead dims {tuple(lead)} needs x of shape "
            f"(*lead, C, K); got {tuple(x.shape)}")
    k_dim = x.shape[-1]
    out_dtype = out_dtype or x.dtype

    if fsdp_axes:
        # the leaf is a local shard (block axis nb still FSDP-split), so the
        # recorded k_dim does not apply until after the gather
        info = LeafInfo(k_dim=k_dim, n_out=wleaf["scale"].shape[-1],
                        lead=tuple(lead), fsdp=tuple(fsdp_axes))
        return _sharded_call(wleaf, x, cfg, spec, info, mesh=None,
                             pattern=None, backend=backend,
                             accum_dtype=accum_dtype, out_dtype=out_dtype)

    _check_k(spec, k_dim)
    info = LeafInfo(k_dim=k_dim, n_out=wleaf["scale"].shape[-1],
                    lead=tuple(lead), name="")
    variant, interpret = _pick(cfg, info, spec, backend)
    _note_dispatch(variant, wleaf)
    if variant.grouped:
        packed = _as_packed(wleaf, cfg, k_dim)
        with jax.named_scope(variant.name):
            return variant.fn(x, packed, out_dtype=out_dtype,
                              interpret=interpret, accum_dtype=accum_dtype)
    with jax.named_scope(variant.name):
        wd = dequant_leaf(wleaf, x.dtype, cfg=cfg, k_dim=k_dim)
        return jnp.matmul(x, wd, preferred_element_type=accum_dtype or
                          jnp.float32).astype(out_dtype)


def apply(plan, name: str, x: jnp.ndarray, *, backend: Optional[str] = None,
          **kw) -> jnp.ndarray:
    """Name-keyed plan execution: y = x @ dequant(plan[name]).

    Stacked serving-layout entries (MoE expert stacks) route through
    :func:`dispatch_grouped` — ``x`` must then carry the matching lead dims.
    Column-folded entries fold lead dims into output channels, so a 3-D+
    original shape cannot be served as a matmul at all.
    """
    entry = plan.entries[name]
    if entry.leaf is None:
        raise ValueError(f"plan entry {name!r} is selection-only "
                         f"(built with pack=False)")
    if entry.layout == "folded" and len(entry.shape) > 2:
        raise ValueError(
            f"{name!r} folded a {len(entry.shape)}-D weight of shape "
            f"{entry.shape} into columns; apply() would return "
            f"column-folded output — use plan[{name!r}].dequantized()")
    return dispatch(entry.leaf, x, backend=backend, **kw)


def dequant_leaf(wleaf, dtype=jnp.bfloat16,
                 cfg: Optional[StruMConfig] = None,
                 k_dim: Optional[int] = None) -> jnp.ndarray:
    """Decompress a (possibly stacked) packed leaf to dense weights.

    Non-dict leaves pass through — callers can feed any mix of packed and
    dense stacks (a heterogeneous schedule may pack any subset).  Stacked
    payloads (lead dims, e.g. MoE expert stacks ``(E, nb, rows, N)``) are
    vmapped over their lead axes.

    The true (unpadded) K comes from, in order: the explicit ``k_dim``
    argument, the leaf's embedded ``spec`` (plan-built leaves record it),
    or — last resort, legacy hand-built leaves only — the padded payload
    (``nb * w``).  The padded derivation is only correct when ``K % w == 0``:
    padding rows decode to *nonzero* junk (MIP2Q code 0 is ±2⁰·scale), so
    plan-built stacks always carry the exact K.
    """
    if not isinstance(wleaf, dict):
        return wleaf
    cfg, spec = leaf_spec(wleaf, cfg)
    lead_dims = wleaf["mask"].ndim - 3
    if k_dim is None:
        k_dim = getattr(spec, "k_dim", None)
    if k_dim is None:
        k_dim = wleaf["mask"].shape[-3] * cfg.w

    def one(mask, hi, lo, scale):
        p = packing.PackedStruM(
            method=cfg.method, w=cfg.w, n_low=cfg.n_low, q=cfg.q, L=cfg.L,
            k_dim=k_dim, scale=scale, mask=mask, hi=hi, lo=lo)
        return packing.dequantize(p, dtype)

    if lead_dims == 0:
        return one(wleaf["mask"], wleaf["hi"], wleaf["lo"], wleaf["scale"])
    lead = wleaf["mask"].shape[:lead_dims]
    g = math.prod(lead)   # explicit: -1 breaks on 0-row payloads (sparsity)
    fields = [wleaf["mask"], wleaf["hi"], wleaf["lo"], wleaf["scale"]]
    flat = [f.reshape((g,) + f.shape[lead_dims:]) for f in fields]
    dq = jax.vmap(one)(*flat)
    return dq.reshape(tuple(lead) + dq.shape[1:])
