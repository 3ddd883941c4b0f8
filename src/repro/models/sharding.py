"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Every parameter is declared with *logical* axis names; rules map those to
mesh axes.  Defaults implement FSDP(+pod) × TP:

  * the ``embed``-like (reduction / d_model) dim of every weight shards over
    the data axis → ZeRO-3/FSDP storage, all-gathered per use by SPMD,
  * output-feature dims (heads, mlp, vocab, experts) shard over ``model``,
  * stacked-layer scan dims never shard.

Activations: batch shards over data(+pod); attention heads / mlp over
model; decode-time KV caches shard their *sequence* dim over model
(flash-decode style — softmax and A·V reductions become small collectives
instead of giant gathers).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "Rules", "TRAIN_RULES", "POD_TRAIN_RULES", "rules_for_mesh", "fsdp_axes",
    "spec_for_axes", "shard_leaf", "constrain", "batch_spec", "shard_map",
]


def fsdp_axes(mesh) -> tuple:
    """Mesh axes weights FSDP-shard (and all-gather) over: ``("data",)``, or
    ``("pod", "data")`` when FSDP spans pods.

    The single source of truth for the gather/batch axis derivation —
    ``engine.sharded`` (compressed FSDP gathers), ``models.moe`` (expert
    gathers + pmean), and ``launch.specs`` (batch sharding) all consume it.
    Works with any mesh-like object exposing ``axis_names``; returns ``()``
    for ``mesh=None``.
    """
    names = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    cand = ("pod", "data") if "pod" in names else ("data",)
    return tuple(a for a in cand if a in names)


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

# logical axis -> mesh axis (or tuple of mesh axes); None = replicated
TRAIN_RULES: dict = {
    "batch": "data",
    "seq": None,
    "embed": "data",        # FSDP shard dim of weights
    "embed_no_fsdp": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": None,       # GQA kv counts are small; replicate
    "head_dim": None,
    "qkv": "model",         # fused (heads*hd [+bias]) output dims
    "mlp": "model",
    "experts": "model",     # EP == TP axis (DESIGN.md §3)
    "expert_mlp": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "ssm_heads": "model",
    "layers": None,
    "conv": None,
    "cache_seq": "model",   # decode KV/conv caches: sequence over model
}

POD_TRAIN_RULES = dict(TRAIN_RULES)
POD_TRAIN_RULES.update({
    "batch": ("pod", "data"),
    "embed": ("pod", "data"),   # FSDP spans pods: weights shard over all 512
})


class Rules:
    def __init__(self, table: dict):
        self.table = dict(table)

    def __call__(self, axes) -> P:
        return spec_for_axes(axes, self.table)


def rules_for_mesh(mesh: Optional[Mesh], global_batch: Optional[int] = None) -> Rules:
    table = dict(POD_TRAIN_RULES if (
        mesh is not None and "pod" in mesh.axis_names) else TRAIN_RULES)
    if mesh is not None and global_batch is not None:
        import math
        baxes = table["batch"]
        baxes = baxes if isinstance(baxes, tuple) else (baxes,)
        n = math.prod(mesh.shape[a] for a in baxes)
        if global_batch % n:
            table["batch"] = None  # e.g. long_500k B=1: replicate batch;
            # the model axis still shards cache_seq / heads
    return Rules(table)


def spec_for_axes(axes, table: dict) -> P:
    """('embed','mlp') -> PartitionSpec('data','model') under the rules."""
    out = []
    for a in axes:
        if a is None:
            out.append(None)
            continue
        m = table.get(a, None)
        out.append(m)
    return P(*out)


def shard_leaf(mesh: Optional[Mesh], x, axes, table: Optional[dict] = None):
    """Device-put / constrain one array to its logical spec (test helper)."""
    if mesh is None:
        return x
    table = table or TRAIN_RULES
    return jax.device_put(x, NamedSharding(mesh, spec_for_axes(axes, table)))


def constrain(x, axes, rules: Optional[Rules]):
    """with_sharding_constraint by logical axes; no-op without rules."""
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules(axes))


def batch_spec(rules: Optional[Rules], extra_axes: int = 1) -> P:
    """(batch, seq, ...) activation spec."""
    if rules is None:
        return P()
    return rules(("batch",) + (None,) * extra_axes)
