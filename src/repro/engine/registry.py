"""Schedule-aware kernel registry: named variants + capability predicates.

The paper's DPU executes each layer on a statically configured PE variant
(Fig. 9, Fig. 13); the software analog is a registry of specialized
lowerings keyed by what each :class:`StruMConfig` actually needs.  Variant
selection is *data-driven* — a variant declares a ``supports(cfg, info)``
predicate and a priority, and :func:`select_variant` picks the
highest-priority supported one — so new backends (grouped MoE matmul,
sharded kernels) slot in as registry entries instead of new if/else chains
in call sites.

Families map to execution substrates:

  ``pallas``     compressed-stream Pallas kernels (Mosaic on TPU, interpret
                 elsewhere) — the paper's accelerated PE.
  ``xla``        dequantize-to-dense + XLA dot; portable under pjit/TP, the
                 fallback for anything the Pallas path cannot express.
  ``reference``  the pure-jnp oracle (tests, debugging).

The ``backend`` string used across the engine API resolves to a family plus
an execution mode: ``"auto"`` (pallas on TPU, xla elsewhere), ``"pallas"``,
``"interpret"`` (pallas with interpret=True, overriding
``kernels.ops.default_interpret`` per call), ``"xla"``, ``"reference"``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, NamedTuple, Optional

import jax

from repro.core.policy import StruMConfig

__all__ = [
    "LeafInfo", "KernelVariant", "ExecSpec", "ShardSpec", "BACKENDS",
    "register_kernel", "unregister_kernel", "get_variant", "list_variants",
    "select_variant", "resolve_backend",
]

BACKENDS = ("auto", "pallas", "interpret", "xla", "reference")


class LeafInfo(NamedTuple):
    """Static shape facts a capability predicate may condition on."""

    k_dim: int                 # reduction dim (unpadded)
    n_out: int                 # output channels
    lead: tuple = ()           # leading stack dims (experts / scan groups)
    name: str = ""             # parameter path name, for diagnostics
    fsdp: tuple = ()           # mesh axes the reduction/block dim is
                               # FSDP-sharded over; non-empty selects from
                               # the ``sharded:*`` variant family
    tp_pattern: Optional[str] = None  # 'col' | 'row' TP layout (2-D leaves)
    cache: bool = False        # True selects from the ``cache:*`` family
                               # (paged KV-page codecs: k_dim is the page
                               # size, n_out the per-token feature dim)
    attn: bool = False         # True selects the fused-attention partition
                               # of the cache family (``cache:attn_*``):
                               # page-pool consumers that run the whole
                               # QK^T / softmax / AV loop, not bare codecs
    draft: str = ""            # non-empty selects from the ``draft:*``
                               # family — reduced-fidelity lowerings over
                               # the same packed payload ("histream" |
                               # "maskfree_p"); the speculative draft lane


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One registered lowering of the quantized matmul.

    ``fn(x2, packed, *, out_dtype, interpret, accum_dtype) -> y2`` operates
    on flattened ``(M, K)`` activations and a :class:`PackedStruM`; wrappers
    ignore kwargs their substrate has no use for (xla ignores ``interpret``,
    pallas ignores ``accum_dtype`` — it always accumulates f32 in the MXU).

    ``grouped=True`` marks a variant whose ``fn`` contracts *stacked* leaves:
    it takes ``(lead..., M, K)`` activations plus a PackedStruM whose payload
    fields carry the same lead dims, and returns ``(lead..., M, N)``.  Its
    ``supports`` predicate should require ``info.lead`` — the two shapes are
    disjoint, so grouped and 2-D variants never compete for the same leaf.

    ``cache=True`` marks a KV-page codec (the ``cache:*`` family): its ``fn``
    decodes a batch of packed cache pages back to values —
    ``fn(leaf, *, cfg, page_size, out_dtype, interpret) -> pages`` — rather
    than contracting activations.  Selection only considers cache variants
    when ``info.cache`` is set, so page codecs and matmul lowerings never
    compete for the same leaf.

    ``attn=True`` (implies ``cache=True``) marks a fused-attention consumer
    of the page pools (the ``cache:attn_*`` partition): its ``fn`` computes
    the *sealed-page partial* of paged attention —
    ``fn(pool, qf, page_table, n_valid, *, cfg, spec, backend, interpret)
    -> (acc, m, l)`` — returning an unnormalized online-softmax state
    rather than decoded pages.  ``info.attn`` partitions selection the same
    way ``info.cache`` does, so page codecs and attention consumers never
    compete for the same call site.

    ``sharded=True`` marks a distributed variant (the ``sharded:*`` family):
    its ``fn`` takes the raw payload dict + activations plus mesh context
    (``fn(wleaf, x, *, cfg, mesh, fsdp, pattern, k_dim, backend, interpret,
    accum_dtype, out_dtype)``) and owns its collectives.  Selection only
    considers sharded variants when ``info.fsdp`` is non-empty, so sharded
    and single-device variants never compete either.  ``redispatch=True``
    marks a sharded wrapper that re-enters variant selection *after* its
    gather with the caller's backend — cross-family fallback onto such a
    variant is not a datapath substitution and emits no warning.

    ``draft=True`` marks a reduced-fidelity lowering (the ``draft:*``
    family): same ``fn`` contract as a 2-D matmul variant, but it streams a
    strict subset of the packed payload's fields (skipping lo, or mask+lo).
    Selection only considers draft variants when ``info.draft`` names a
    mode, so full-fidelity and draft lowerings never compete — a draft
    variant's ``supports`` should additionally match ``info.draft`` so the
    modes don't compete with each other.

    ``grid_steps(m, packed, x_bytes)``, where set, gives the grid steps of
    one call over ``m`` rows (the trace-time ``dispatch/grid_steps``
    counter).
    """

    name: str
    fn: Callable
    supports: Callable[[StruMConfig, LeafInfo], bool]
    family: str = "pallas"
    priority: int = 0
    description: str = ""
    grouped: bool = False
    sharded: bool = False
    redispatch: bool = False
    cache: bool = False
    attn: bool = False
    draft: bool = False
    grid_steps: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Static per-leaf distributed layout recorded by a mesh-aware plan.

    Axis *names* only (hashable, mesh-object-free): the runtime mesh still
    arrives per call — a plan built for an 8-device FSDP×TP layout serves on
    any mesh with the same axis names.
    """

    fsdp_axes: tuple = ()             # mesh axes the reduction (2-D leaves)
                                      # or packed-block (stacks) dim shards
                                      # over; the compressed-gather axes
    tp_pattern: Optional[str] = None  # 'col' (K FSDP / N TP) or 'row'
                                      # (K TP / N FSDP) for 2-D leaves
    lead_axis: Optional[str] = None   # mesh axis an expert stack's lead dim
                                      # shards over (EP == TP axis)


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """Static per-leaf execution metadata embedded in packed param leaves.

    Registered as a static pytree node (like the ``StruMConfig`` it wraps),
    so it rides the jit treedef: heterogeneous per-layer variants flow
    through the unmodified forward with zero traced leaves.
    """

    cfg: StruMConfig
    variant: str
    backend: Optional[str] = None   # plan-level backend the variant was
                                    # selected under (None = auto)
    k_dim: Optional[int] = None     # true (unpadded) reduction dim — packed
                                    # payloads only know ceil(K/w)*w, so
                                    # stacked dequant needs this to slice off
                                    # block-padding rows (which decode to
                                    # junk, not zero, under MIP2Q)
    shard: Optional[ShardSpec] = None  # distributed layout (mesh-aware plans)


try:
    jax.tree_util.register_static(ExecSpec)
except ValueError:
    pass  # already registered (module reload)


_REGISTRY: dict[str, KernelVariant] = {}


def register_kernel(name: str, *, supports: Callable, family: str = "pallas",
                    priority: int = 0, description: str = "",
                    grouped: bool = False, sharded: bool = False,
                    redispatch: bool = False, cache: bool = False,
                    attn: bool = False, draft: bool = False,
                    grid_steps: Optional[Callable] = None):
    """Decorator: register ``fn`` as kernel variant ``name``.

    Re-registering a name replaces the previous entry (latest wins), so a
    downstream package can shadow a built-in with a tuned lowering.
    """
    if family not in ("pallas", "xla", "reference"):
        raise ValueError(f"unknown family {family!r}")
    if attn and not cache:
        raise ValueError("attn=True variants live in the cache family; "
                         "pass cache=True as well")

    def deco(fn):
        _REGISTRY[name] = KernelVariant(
            name=name, fn=fn, supports=supports, family=family,
            priority=priority, description=description, grouped=grouped,
            sharded=sharded, redispatch=redispatch, cache=cache, attn=attn,
            draft=draft, grid_steps=grid_steps)
        return fn
    return deco


def unregister_kernel(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_variant(name: str) -> KernelVariant:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel variant {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def list_variants() -> dict[str, KernelVariant]:
    return dict(_REGISTRY)


def resolve_backend(backend: Optional[str]) -> tuple[str, Optional[bool]]:
    """``backend`` string -> (family, interpret flag).

    ``interpret=None`` defers to :func:`repro.kernels.ops.default_interpret`
    at call time; ``True`` forces interpret mode for this call.
    """
    backend = backend or "auto"
    if backend == "auto":
        # pallas only where it compiles natively; interpret mode is an
        # explicit opt-in (orders of magnitude slower than an XLA dot)
        fam = "pallas" if jax.default_backend() == "tpu" else "xla"
        return fam, None
    if backend == "pallas":
        return "pallas", None
    if backend == "interpret":
        return "pallas", True
    if backend in ("xla", "reference"):
        return backend, None
    raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")


def select_variant(cfg: StruMConfig, info: LeafInfo,
                   backend: Optional[str] = None) -> KernelVariant:
    """Pick the highest-priority variant whose predicate accepts (cfg, info).

    Within the resolved family first; if the family has no supporting
    variant (e.g. a stacked expert leaf under ``backend="pallas"``), fall
    back to the ``xla`` family rather than failing — the dequant path can
    express everything.

    Mesh context partitions the candidate set: a non-empty ``info.fsdp``
    restricts selection to ``sharded=True`` variants (which own their
    collectives), an empty one excludes them — distributed and local
    lowerings never compete for the same leaf.  ``info.cache`` partitions
    the same way: page codecs (``cache:*``) only compete with each other.
    """
    fam, _ = resolve_backend(backend)
    sharded = bool(info.fsdp)
    cache = bool(getattr(info, "cache", False))
    attn = bool(getattr(info, "attn", False))
    draft = bool(getattr(info, "draft", ""))
    for family in dict.fromkeys((fam, "xla")):
        cands = [v for v in _REGISTRY.values()
                 if v.family == family and v.sharded == sharded
                 and v.cache == cache and v.attn == attn
                 and v.draft == draft
                 and v.supports(cfg, info)]
        if cands:
            best = max(cands, key=lambda v: (v.priority, v.name))
            if family != fam and backend not in (None, "auto") \
                    and not best.redispatch:
                # an explicitly requested family had no supporting variant
                # — substitution should be visible (stacked leaves now have
                # the pallas:grouped* family, so they warn like 2-D leaves
                # when, e.g., w % 8 != 0 forces the dequant fallback).
                # redispatch=True wrappers re-select post-gather with the
                # same backend, so landing on one is not a substitution.
                warnings.warn(
                    f"backend={backend!r} has no variant supporting "
                    f"{cfg.method} w={cfg.w} n_low={cfg.n_low} "
                    f"({info.name or 'leaf'}); falling back to {family!r}",
                    stacklevel=2)
            return best
    raise LookupError(
        f"no registered kernel variant supports cfg={cfg} info={info} "
        f"backend={backend!r} (registered: {sorted(_REGISTRY)})")
