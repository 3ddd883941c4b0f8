"""Specs-first parameter system.

Every module declares its parameters as ``ParamDef(shape, dtype,
logical_axes)`` trees.  From one definition tree we derive
  * materialized params (``init_params`` — deterministic per-path PRNG),
  * ``jax.ShapeDtypeStruct`` stand-ins for the dry-run (no allocation),
  * ``PartitionSpec`` / ``NamedSharding`` trees for pjit in_shardings.

This keeps model code, dry-run, and launcher in exact agreement about
shapes and shardings.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.models.sharding import Rules, spec_for_axes

__all__ = ["ParamDef", "init_params", "abstract_params", "param_pspecs",
           "param_shardings", "tree_bytes"]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    axes: tuple                  # logical axis names, len == len(shape)
    dtype: str = "float32"
    init: str = "normal"         # normal | zeros | ones | embed
    scale: float = 1.0           # stddev multiplier for normal inits

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _fan_in(shape) -> int:
    return int(np.prod(shape[:-1])) if len(shape) > 1 else max(shape[0], 1)


def init_params(defs, seed: int = 0, dtype_override: Optional[str] = None,
                shardings=None):
    """Materialize a ParamDef tree.  Deterministic: each leaf's key is
    fold_in(seed, crc32(path)) — stable across processes/hosts (unlike
    ``hash(str)``, which Python salts per process).

    ``shardings`` (e.g. :func:`param_shardings`) draws every leaf directly
    in its sharded layout, so a model larger than one device never lands
    whole on the first; the values do not depend on it.
    """
    flat = jax.tree_util.tree_flatten_with_path(defs, is_leaf=_is_def)
    leaves, treedef = flat
    shard_leaves = ([None] * len(leaves) if shardings is None else
                    treedef.flatten_up_to(shardings))
    out = []
    root = jax.random.PRNGKey(seed)
    for (path, d), sharding in zip(leaves, shard_leaves):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        make = functools.partial(_draw, d, _resolve_dtype(d, dtype_override))
        out.append(make(key) if sharding is None else
                   jax.jit(make, out_shardings=sharding)(key))
    return jax.tree_util.tree_unflatten(treedef, out)


def _draw(d: ParamDef, dt, key):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    std = d.scale / np.sqrt(_fan_in(d.shape)) if d.init == "normal" else d.scale
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dt)


def _resolve_dtype(d: ParamDef, override: Optional[str]):
    """Overrides apply to floating leaves only (packed uint8/int8 payloads
    and integer counters keep their declared dtype)."""
    base = jnp.dtype(d.dtype)
    if override is None or not jnp.issubdtype(base, jnp.floating):
        return base
    return jnp.dtype(override)


def abstract_params(defs, dtype_override: Optional[str] = None):
    """ShapeDtypeStruct tree — the dry-run stand-in (zero allocation)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, _resolve_dtype(d, dtype_override)),
        defs, is_leaf=_is_def)


def param_pspecs(defs, rules: Rules):
    return jax.tree_util.tree_map(
        lambda d: spec_for_axes(d.axes, rules.table), defs, is_leaf=_is_def)


def param_shardings(defs, mesh: Mesh, rules: Rules):
    return jax.tree_util.tree_map(
        lambda d: NamedSharding(mesh, spec_for_axes(d.axes, rules.table)),
        defs, is_leaf=_is_def)


def tree_bytes(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=_is_def)
    return sum(int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize for d in leaves)
