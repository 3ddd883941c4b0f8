"""Seeded weights, drawn on the device in the type they are served in.

Each leaf is named by its path in the program's parameter tree and each
layer of a stacked leaf has a key of its own,
``fold_in(fold_in(PRNGKey(seed), crc32(name)), layer)``, so the plain
reference can draw one layer at a time and get the same values that the
set-up drew for the whole stack.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp

from bench.lib.traffic import jax_seed


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter: path name, per-layer shape, number of stacked layers
    (0 for an unstacked leaf), and its draw ``mean + std * N(0, 1)``."""

    name: str
    shape: tuple
    layers: int
    std: float
    mean: float = 0.0


def leaf_key(seed: int, name: str):
    root = jax.random.PRNGKey(jax_seed(seed))
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _one(key, layer, shape, std, mean):
    k = jax.random.fold_in(key, layer)
    x = jax.random.normal(k, shape, jnp.float32) * std + mean
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("shape", "layers", "std", "mean"))
def _draw_stack(key, *, shape, layers, std, mean):
    return jax.lax.map(lambda l: _one(key, l, shape, std, mean),
                       jnp.arange(layers, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("shape", "std", "mean"))
def _draw_layer(key, layer, *, shape, std, mean):
    return _one(key, layer, shape, std, mean)


def draw(seed: int, leaf: Leaf):
    """The whole leaf, bf16: ``(layers, *shape)`` or ``shape``."""
    key = leaf_key(seed, leaf.name)
    if leaf.layers:
        return _draw_stack(key, shape=tuple(leaf.shape), layers=leaf.layers,
                           std=float(leaf.std), mean=float(leaf.mean))
    return _draw_layer(key, jnp.int32(0), shape=tuple(leaf.shape),
                       std=float(leaf.std), mean=float(leaf.mean))


def draw_layer(seed: int, leaf: Leaf, layer: int):
    """One layer of a stacked leaf (the whole leaf when unstacked), bf16."""
    return _draw_layer(leaf_key(seed, leaf.name), jnp.int32(layer),
                       shape=tuple(leaf.shape), std=float(leaf.std),
                       mean=float(leaf.mean))
