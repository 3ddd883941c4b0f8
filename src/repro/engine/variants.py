"""Built-in kernel variants (imported for side effect by ``repro.engine``).

Each wraps an existing lowering behind the uniform variant signature
``fn(x2, packed, *, out_dtype, interpret, accum_dtype) -> y2``:

  pallas:maskfree   p = 1.0 — lo payload only, no mask/hi stream
  pallas:dense      n_low = 0 — hi payload only; works for any ``w``
  pallas:onehot     general one-hot scatter decode (needs ``w % 8 == 0``)
  pallas:grouped            stacked (expert / scan) leaves — lead grid axis,
                            same one-hot decode per group
  pallas:grouped_maskfree   stacked, p = 1.0
  pallas:grouped_dense      stacked, n_low = 0 (any ``w``)
  xla:dequant       dequantize + XLA dot — the portable fallback for both
                    2-D and stacked leaves (stacks dequant + batched dot)
  ref:jnp           pure-jnp oracle (``kernels.ref``)

Specializations carry higher priority than the general Pallas path, so
selection prefers the cheapest decoder that can express the config.  The
``pallas:grouped*`` family only accepts ``info.lead != ()``; stacks whose
config no grouped variant expresses (``w % 8 != 0`` with a mixed payload)
still fall back to ``xla:dequant``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core import packing
from repro.engine.registry import register_kernel
from repro.kernels import ops, ref


def _two_d(cfg, info):
    return not info.lead


def _stacked(cfg, info):
    return bool(info.lead)


def _steps(variant):
    """``grid_steps`` of a 2-D lowering or draft mode: its call's grid as
    :func:`ops.grid_plan` lays it out."""
    return functools.partial(ops.grid_steps, variant=variant)


@register_kernel(
    "pallas:onehot", family="pallas", priority=10,
    supports=lambda cfg, info: _two_d(cfg, info) and cfg.w % 8 == 0,
    description="general in-VMEM decode: mask unpack + one-hot scatter",
    grid_steps=_steps("onehot"))
def _onehot(x2, packed, *, out_dtype=None, interpret=None, accum_dtype=None):
    return ops.strum_matmul(x2, packed, out_dtype=out_dtype,
                            interpret=interpret, variant="onehot")


@register_kernel(
    "pallas:maskfree", family="pallas", priority=20,
    supports=lambda cfg, info: (_two_d(cfg, info) and cfg.n_low == cfg.w
                                and cfg.method in ("dliq", "mip2q")),
    description="p=1.0: decode lo fields in order, no mask/hi stream",
    grid_steps=_steps("maskfree"))
def _maskfree(x2, packed, *, out_dtype=None, interpret=None, accum_dtype=None):
    return ops.strum_matmul(x2, packed, out_dtype=out_dtype,
                            interpret=interpret, variant="maskfree")


@register_kernel(
    "pallas:dense", family="pallas", priority=20,
    supports=lambda cfg, info: _two_d(cfg, info) and cfg.n_low == 0,
    description="n_low=0: hi payload is the block in order; reshape + scale",
    grid_steps=_steps("dense"))
def _dense(x2, packed, *, out_dtype=None, interpret=None, accum_dtype=None):
    return ops.strum_matmul(x2, packed, out_dtype=out_dtype,
                            interpret=interpret, variant="dense")


@register_kernel(
    "pallas:grouped", family="pallas", priority=10, grouped=True,
    supports=lambda cfg, info: _stacked(cfg, info) and cfg.w % 8 == 0,
    description="stacked expert/scan leaves: lead grid axis, one-hot decode")
def _grouped(xg, packed, *, out_dtype=None, interpret=None, accum_dtype=None):
    return ops.strum_grouped_matmul(xg, packed, out_dtype=out_dtype,
                                    interpret=interpret, variant="onehot")


@register_kernel(
    "pallas:grouped_maskfree", family="pallas", priority=20, grouped=True,
    supports=lambda cfg, info: (_stacked(cfg, info) and cfg.n_low == cfg.w
                                and cfg.method in ("dliq", "mip2q")),
    description="stacked p=1.0: per-group lo payload only, no mask/hi stream")
def _grouped_maskfree(xg, packed, *, out_dtype=None, interpret=None,
                      accum_dtype=None):
    return ops.strum_grouped_matmul(xg, packed, out_dtype=out_dtype,
                                    interpret=interpret, variant="maskfree")


@register_kernel(
    "pallas:grouped_dense", family="pallas", priority=20, grouped=True,
    supports=lambda cfg, info: _stacked(cfg, info) and cfg.n_low == 0,
    description="stacked n_low=0: per-group hi payload in order; any w")
def _grouped_dense(xg, packed, *, out_dtype=None, interpret=None,
                   accum_dtype=None):
    return ops.strum_grouped_matmul(xg, packed, out_dtype=out_dtype,
                                    interpret=interpret, variant="dense")


# ------------------------------------------------------------------ draft --
#
# Reduced-fidelity lowerings over the SAME packed payload (self-speculative
# decoding's free draft model).  Selection is partitioned by ``info.draft``
# (a mode string set by ``engine.draft.build_draft_plan``), and each
# variant's predicate pins its own mode so the two never compete.  The xla
# twins decode only the streamed fields, so the draft lane keeps its
# byte-subset property on every backend.

def _draft_mode(mode):
    def pred(cfg, info):
        return (_two_d(cfg, info)
                and getattr(info, "draft", "") == mode
                and 0 < cfg.n_low < cfg.w)
    return pred


@register_kernel(
    "draft:histream", family="pallas", priority=10, draft=True,
    supports=lambda cfg, info: _draft_mode("histream")(cfg, info)
    and cfg.w % 8 == 0,
    description="draft: mask+hi stream only, lo decodes to zero",
    grid_steps=_steps("histream"))
def _draft_histream(x2, packed, *, out_dtype=None, interpret=None,
                    accum_dtype=None):
    return ops.strum_matmul_draft(x2, packed, mode="histream",
                                  out_dtype=out_dtype, interpret=interpret)


@register_kernel(
    "draft:maskfree_p", family="pallas", priority=10, draft=True,
    supports=_draft_mode("maskfree_p"),
    description="draft: hi stream only, block treated as all-high",
    grid_steps=_steps("maskfree_p"))
def _draft_maskfree_p(x2, packed, *, out_dtype=None, interpret=None,
                      accum_dtype=None):
    return ops.strum_matmul_draft(x2, packed, mode="maskfree_p",
                                  out_dtype=out_dtype, interpret=interpret)


def _draft_xla(mode):
    from repro.engine.draft import draft_dequant_packed

    def fn(x2, packed, *, out_dtype=None, interpret=None,
           accum_dtype=jnp.float32):
        out_dtype = out_dtype or x2.dtype
        wd = draft_dequant_packed(packed, mode, x2.dtype)
        return jnp.dot(x2, wd,
                       preferred_element_type=accum_dtype or jnp.float32
                       ).astype(out_dtype)
    return fn


register_kernel(
    "draft:xla_histream", family="xla", priority=0, draft=True,
    supports=lambda cfg, info: _draft_mode("histream")(cfg, info)
    and cfg.w % 8 == 0,
    description="draft fallback: mask+hi decode + XLA dot, lo never read")(
        _draft_xla("histream"))

register_kernel(
    "draft:xla_maskfree_p", family="xla", priority=0, draft=True,
    supports=_draft_mode("maskfree_p"),
    description="draft fallback: hi-only decode + XLA dot, mask/lo never "
                "read")(_draft_xla("maskfree_p"))


@register_kernel(
    "xla:dequant", family="xla", priority=0,
    supports=lambda cfg, info: True,
    description="dequantize to dense, fused XLA dot (portable fallback)")
def _dequant(x2, packed, *, out_dtype=None, interpret=None,
             accum_dtype=jnp.float32):
    out_dtype = out_dtype or x2.dtype
    wd = packing.dequantize(packed, x2.dtype)
    return jnp.dot(x2, wd,
                   preferred_element_type=accum_dtype or jnp.float32
                   ).astype(out_dtype)


@register_kernel(
    "ref:jnp", family="reference", priority=0,
    supports=_two_d,
    description="pure-jnp oracle (kernels.ref.strum_matmul_ref)")
def _reference(x2, packed, *, out_dtype=None, interpret=None,
               accum_dtype=None):
    return ref.strum_matmul_ref(x2, packed, out_dtype=out_dtype or x2.dtype)
