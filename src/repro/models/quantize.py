"""Model-level StruM integration: serving-layout packing + TP gather paths.

The tree walk that used to live here (``strum_serve_params``) is now a
deprecated shim over :func:`repro.engine.build_plan`; this module keeps the
pieces the engine builds on:

``_pack_leaf``        (..., K, N) kernel -> compressed serving-layout arrays
                      (lead dims preserved so ``lax.scan`` / expert indexing
                      slice them exactly like dense params).
``packed_model_defs`` dry-run ParamDefs with exact packed shapes/shardings.

The TP/FSDP compressed-gather path lives in the engine's ``sharded:*``
registry family (:mod:`repro.engine.sharded`); the old ``gather_dequant``
shim here is gone — call ``engine.dispatch(leaf, x, mesh=...,
tp_pattern=...)`` or ``repro.engine.sharded.gather_dequant_leaf``.

The model's ``linear`` recognizes compressed leaves and dispatches through
:mod:`repro.engine` — no other model code changes, which is the point:
StruM is a storage/bandwidth transform, not an architecture change.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import blocking, packing
from repro.core.policy import LayerPolicy, StruMConfig, default_policy
from repro.core.quantizers import int8_symmetric, quantize_blocks

__all__ = ["strum_serve_params", "serve_tree_bytes"]

# StruMConfig rides inside compressed param subtrees as the per-leaf static
# metadata carrier (the schedule's per-layer PE programming, Fig. 9).
# Registering it static makes it part of the jit treedef — hashable config,
# zero traced leaves — so heterogeneous per-layer configs flow through the
# unmodified forward.
try:
    jax.tree_util.register_static(StruMConfig)
except ValueError:
    pass  # already registered (module reload)


@functools.partial(jax.jit, static_argnames=("scfg",))
def _pack_2d(w: jnp.ndarray, scfg: StruMConfig) -> dict:
    """One (K, N) kernel -> its payload arrays, as one compiled program
    (a whole-model plan packs each weight shape once, not op by op)."""
    codes, scale = int8_symmetric(w, axis=0)
    blocks = blocking.to_blocks(codes, scfg.w)
    qb = quantize_blocks(blocks, scfg.method, scfg.n_low, q=scfg.q, L=scfg.L)
    p = packing.pack(qb, method=scfg.method, scale=scale, k_dim=w.shape[0],
                     n_low=scfg.n_low, q=scfg.q, L=scfg.L)
    return {"mask": p.mask, "hi": p.hi, "lo": p.lo, "scale": p.scale}


def _pack_leaf(wt: jnp.ndarray, scfg: StruMConfig) -> dict:
    """(..., K, N) kernel -> compressed arrays with lead dims preserved.

    Lead dims (scan groups, experts) are kept as leading axes of every
    payload array so `lax.scan` can slice them exactly like dense params.
    """
    lead = wt.shape[:-2]
    k, n = wt.shape[-2:]
    w2 = wt.reshape((-1, k, n))
    packed = [_pack_2d(w2[i], scfg) for i in range(w2.shape[0])]
    return {key: jnp.stack([p[key] for p in packed]).reshape(
        lead + packed[0][key].shape) for key in packed[0]}


def strum_serve_params(params, cfg, policy: Optional[LayerPolicy] = None,
                       schedule=None):
    """Deprecated shim over :func:`repro.engine.build_plan` — returns
    ``build_plan(...).params`` (the model-shaped served tree).

    Without a ``schedule``, every eligible kernel gets the uniform
    ``cfg.strum`` (the paper's statically-configured PE).  With one (a
    :class:`repro.autotune.schedule.StruMSchedule`, e.g. loaded from disk),
    each tensor gets *its own* config — the dynamically-configurable-PE
    deployment — and the chosen config + selected kernel variant are
    embedded in the compressed leaf as static metadata, so the model's
    ``linear`` needs no global config.
    """
    import warnings

    warnings.warn(
        "strum_serve_params is deprecated; use repro.engine.build_plan — "
        "the ExecutionPlan additionally records per-leaf kernel variants",
        DeprecationWarning, stacklevel=2)
    scfg = cfg.strum
    assert scfg is not None or schedule is not None, \
        "set cfg.strum or pass a schedule"
    from repro.engine import build_plan
    return build_plan(params, schedule=schedule,
                      policy=policy if schedule is None else None,
                      cfg=scfg).params


def packed_model_defs(cfg, policy: Optional[LayerPolicy] = None):
    """ParamDef tree for a StruM-compressed model — the dry-run stand-in for
    packed serving (zero allocation, exact payload shapes/shardings).

    Every eligible linear ``{"w": ParamDef(..., (..., in_ax, out_ax))}``
    becomes ``{"w": {"mask", "hi", "lo", "scale"}}`` with the in-axis
    sharding moved to the block dim (nb = K/w) and the out-axis kept — so
    FSDP gathers and HBM streams move the COMPRESSED bytes (r× fewer).
    MoE expert stacks pack the same way (lead dims preserved) and serve
    through the grouped registry family (``engine.dispatch_grouped``).
    """
    import math as _math

    from repro.models import model_defs as _model_defs
    from repro.models.params import ParamDef as _PD

    scfg = cfg.strum
    assert scfg is not None
    policy = policy or default_policy(scfg)
    defs = _model_defs(cfg)

    def visit(path, leaf):
        if not isinstance(leaf, _PD):
            return leaf
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        is_expert = "/moe/" in name and name.rsplit("/", 1)[-1] in ("wi", "wg", "wo")
        if (not name.endswith("/w") and not is_expert) or len(leaf.shape) < 2:
            return leaf
        if not is_expert and policy.resolve(name, leaf.shape) is None:
            return leaf
        lead = leaf.shape[:-2]
        k_dim, n = leaf.shape[-2:]
        la = leaf.axes[:-2]
        in_ax, out_ax = leaf.axes[-2:]
        nb = _math.ceil(k_dim / scfg.w)
        mb, nh, lb = packing.field_dims(scfg.w, scfg.n_low, scfg.q,
                                        scfg.method)
        return {
            "mask": _PD(lead + (nb, mb, n), la + (in_ax, None, out_ax),
                        dtype="uint8", init="zeros"),
            "hi": _PD(lead + (nb, max(nh, 1), n), la + (in_ax, None, out_ax),
                      dtype="int8", init="zeros"),
            "lo": _PD(lead + (nb, max(lb, 1), n), la + (in_ax, None, out_ax),
                      dtype="uint8", init="zeros"),
            "scale": _PD(lead + (1, n), la + (None, out_ax),
                         dtype="float32", init="zeros"),
        }

    return jax.tree_util.tree_map_with_path(visit, defs,
                                            is_leaf=lambda x: isinstance(x, _PD))


def serve_tree_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))
