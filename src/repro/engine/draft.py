"""Draft plans: reduced-fidelity views of a full-fidelity ExecutionPlan.

StruM's packed payload already encodes a *family* of fidelity levels — the
mask/hi/lo streams can be read selectively — so a speculative-decoding
draft model is free: no second checkpoint, no extra HBM residency.  A
:class:`DraftPolicy` names, per leaf, which reduced decode to run:

``histream``    skip the lo stream — hi codes land at their true (masked)
                positions, low positions decode to zero.  Exact for
                ``sparsity`` codecs, a controlled truncation otherwise.
``maskfree_p``  skip mask *and* lo — hi codes fill the leading block
                positions.  Cheapest and lossiest.
``full``        per-leaf escape hatch: keep the target spec.

:func:`build_draft_plan` derives a new :class:`ExecutionPlan` whose param
tree shares every payload array **by identity** with the target plan
(shallow-copied leaf dicts, only the static ``spec`` differs) — zero
additional weight bytes in HBM, which ``repro.analysis`` proves statically
(:func:`~repro.analysis.suite.verify_draft_payload`).  Leaves whose config
no draft variant expresses (stacked expert payloads, ``w % 8 != 0`` for
``histream``, maskfree codecs with no high values) silently keep full
fidelity — the draft is then exact there, never wrong.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.apply import path_name as _path_name
from repro.kernels.ops import DRAFT_MODES, draft_field_set

__all__ = ["DraftPolicy", "build_draft_plan", "draft_dequant_packed",
           "draft_dequant_leaf", "draft_leaf_bytes", "draft_plan_bytes",
           "DRAFT_MODES"]


@dataclasses.dataclass(frozen=True)
class DraftPolicy:
    """Which reduced-fidelity decode each leaf runs in the draft lane.

    ``mode`` is the default for every eligible leaf; ``overrides`` is a
    tuple of ``(substring, mode)`` pairs matched against the leaf's path
    name, first hit wins — ``"full"`` (or ``""``) pins a leaf to the
    target spec.
    """

    mode: str = "histream"
    overrides: tuple = ()

    def __post_init__(self):
        for m in (self.mode,) + tuple(m for _, m in self.overrides):
            if m not in DRAFT_MODES + ("full", ""):
                raise ValueError(f"unknown draft mode {m!r}; want one of "
                                 f"{DRAFT_MODES + ('full',)}")

    def resolve(self, name: str) -> str:
        """The draft mode for ``name`` ('' = keep full fidelity)."""
        for pat, m in self.overrides:
            if pat in name:
                return "" if m in ("", "full") else m
        return "" if self.mode in ("", "full") else self.mode


def draft_dequant_packed(packed: packing.PackedStruM, mode: str,
                         dtype=jnp.float32) -> jnp.ndarray:
    """Reference draft decode of a 2-D packed leaf — reads only the fields
    ``draft_field_set(mode)`` streams (plus scale), exactly like the draft
    Pallas kernels, so jaxprs traced through it keep skipped streams dead.
    """
    w, n = packed.w, packed.n_out
    nb = packed.hi.shape[0]
    if packed.n_low >= w:
        raise ValueError(f"draft modes need high values to stream "
                         f"(n_low={packed.n_low} w={w})")
    if mode == "histream":
        high = packing._unpack_bits_axis(packed.mask, w, axis=1)
        vals = packing._scatter_expand(packed.hi.astype(jnp.float32), high)
    elif mode == "maskfree_p":
        hv = packed.hi.astype(jnp.float32)
        vals = jnp.concatenate(
            [hv, jnp.zeros((nb, w - hv.shape[1], n), jnp.float32)], axis=1)
    else:
        raise ValueError(f"unknown draft mode {mode!r}; "
                         f"want one of {DRAFT_MODES}")
    wd = vals.reshape(nb * w, n) * packed.scale
    return wd[:packed.k_dim].astype(dtype)


def _leaf_packed(leaf: dict, cfg=None, k_dim: Optional[int] = None
                 ) -> packing.PackedStruM:
    spec = leaf.get("spec")
    cfg = cfg or (spec.cfg if spec is not None else leaf.get("cfg"))
    if k_dim is None:
        k_dim = spec.k_dim if spec is not None and spec.k_dim else \
            leaf["mask"].shape[-3] * cfg.w
    return packing.PackedStruM(
        method=cfg.method, w=cfg.w, n_low=cfg.n_low, q=cfg.q, L=cfg.L,
        k_dim=k_dim, scale=leaf["scale"], mask=leaf["mask"], hi=leaf["hi"],
        lo=leaf["lo"])


def draft_dequant_leaf(leaf: dict, mode: str, dtype=jnp.float32,
                       cfg=None, k_dim: Optional[int] = None) -> jnp.ndarray:
    """Draft decode of a packed leaf dict (mode '' = full decode).  Stacked
    payloads (lead dims) are vmapped over, like ``dispatch.dequant_leaf``."""
    if not mode:
        from repro.engine.dispatch import dequant_leaf
        return dequant_leaf(leaf, dtype, cfg=cfg, k_dim=k_dim)
    lead_dims = leaf["mask"].ndim - 3
    if lead_dims == 0:
        return draft_dequant_packed(_leaf_packed(leaf, cfg, k_dim), mode,
                                    dtype)
    lead = leaf["mask"].shape[:lead_dims]
    g = 1
    for d in lead:
        g *= d
    fields = {k: leaf[k].reshape((g,) + leaf[k].shape[lead_dims:])
              for k in ("mask", "hi", "lo", "scale")}

    def one(f):
        return draft_dequant_packed(
            _leaf_packed({**leaf, **f}, cfg, k_dim), mode, dtype)

    dq = jax.vmap(one)(fields)
    return dq.reshape(tuple(lead) + dq.shape[1:])


def draft_leaf_bytes(leaf: dict, mode: str) -> int:
    """HBM payload bytes a draft-mode read of this leaf streams (mode '' =
    the full mask+hi+lo payload).  uint8/int8 fields, so size == bytes."""
    fields = draft_field_set(mode) if mode else ("mask", "hi", "lo")
    return int(sum(leaf[k].size for k in fields))


def _is_packed_leaf(node) -> bool:
    return isinstance(node, dict) and "mask" in node and "hi" in node


def build_draft_plan(plan, policy: Optional[DraftPolicy] = None):
    """Derive the draft-fidelity twin of a full-fidelity plan.

    Returns a new :class:`~repro.engine.plan.ExecutionPlan` whose
    ``params`` tree is the target's with every drafted leaf shallow-copied
    — payload arrays (mask/hi/lo/scale) are the *same objects* as the
    target's, only the static ``spec`` swaps to a ``draft:*`` variant.
    ``meta["draft"]`` records the per-leaf mode map ('' = full fidelity).
    """
    from repro.engine.plan import ExecutionPlan, _is_expert_stack
    from repro.engine.registry import ExecSpec, LeafInfo, select_variant

    policy = policy or DraftPolicy()
    modes: dict = {}
    new_entries = dict(plan.entries)

    def visit(path, leaf):
        if not _is_packed_leaf(leaf):
            return leaf
        name = _path_name(path)
        entry = plan.entries.get(name)
        mode = policy.resolve(name) if entry is not None else ""
        if entry is not None:
            modes[name] = mode
        if not mode:
            return leaf
        # Layer-group stacks are sliced to 2-D before dispatch (scan xs);
        # only expert stacks dispatch with a live lead dim.
        lead = tuple(entry.shape[:-2]) if _is_expert_stack(name) else ()
        info = LeafInfo(k_dim=entry.shape[-2], n_out=entry.shape[-1],
                        lead=lead, name=name, draft=mode)
        try:
            variant = select_variant(entry.cfg, info, backend=plan.backend)
        except LookupError:
            modes[name] = ""              # no draft lowering: stay exact
            return leaf
        spec = ExecSpec(cfg=entry.cfg, variant=variant.name,
                        backend=plan.backend, k_dim=entry.shape[-2])
        new_entries[name] = dataclasses.replace(entry, variant=variant.name)
        return {**leaf, "spec": spec}     # payload arrays shared by identity

    params = jax.tree_util.tree_map_with_path(visit, plan.params,
                                              is_leaf=_is_packed_leaf)
    meta = dict(plan.meta, draft=modes,
                draft_policy={"mode": policy.mode,
                              "overrides": list(map(list, policy.overrides))})
    return ExecutionPlan(entries=new_entries, params=params,
                         backend=plan.backend, scope=plan.scope,
                         schedule=plan.schedule, meta=meta)


def draft_plan_bytes(plan) -> dict:
    """{'draft_bytes', 'full_bytes', 'ratio'} of a draft plan's weight
    reads per full stream (the bandwidth-bound decode cost ratio ``c``)."""
    modes = plan.meta.get("draft", {})
    draft_b = full_b = 0

    def visit(path, leaf):
        nonlocal draft_b, full_b
        if _is_packed_leaf(leaf):
            name = _path_name(path)
            full_b += draft_leaf_bytes(leaf, "")
            draft_b += draft_leaf_bytes(leaf, modes.get(name, ""))
        return leaf

    jax.tree_util.tree_map_with_path(visit, plan.params,
                                     is_leaf=_is_packed_leaf)
    return {"draft_bytes": int(draft_b), "full_bytes": int(full_b),
            "ratio": draft_b / full_b if full_b else 1.0}
