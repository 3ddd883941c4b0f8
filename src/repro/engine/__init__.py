"""``repro.engine`` — unified execution-plan API for quantized serving.

The redesign around one subsystem (ROADMAP: "schedule-aware Pallas kernel
selection"):

* a **kernel registry** (:mod:`registry`) of specialized lowerings with
  capability predicates — ``@register_kernel("pallas:onehot", ...)`` —
  selection is data-driven, not if/else chains at call sites;
* an :class:`ExecutionPlan` (:mod:`plan`) built once from
  ``(params, StruMSchedule)`` recording, per leaf, the packed payload plus
  the *selected* variant;
* a single :func:`dispatch` funnel (:mod:`dispatch`) every quantized matmul
  in ``models/``, ``serving/`` and ``launch/`` goes through, with per-call
  backend override (``backend="interpret"`` forces interpret-mode Pallas).

Typical flow (profile → search → schedule → **plan** → serve):

    from repro import engine
    plan = engine.build_plan(params, schedule=sched)   # or cfg=StruMConfig()
    y = engine.apply(plan, "blocks/pos0/attn/wq/w", x)
    scheduler = BatchScheduler(cfg, params, plan=plan)

Distributed execution is engine-native: ``build_plan(..., mesh=, rules=)``
records per-leaf shardings (:class:`ShardSpec`) and selects from the
``sharded:*`` variant family (:mod:`repro.engine.sharded`) — compressed
FSDP gathers with the per-call ``backend=`` reaching the post-gather
kernel.

KV-cache page codecs are engine-native too: the ``cache:*`` family
(:mod:`repro.engine.cache`) packs/decodes the paged serving runtime's
sealed cache pages through the same registry — ``build_cache_spec``
selects a decoder per ``(codec, page geometry)`` and records it in a
static :class:`CacheSpec`.

The legacy entrypoints (``core.apply.pack_tree`` / ``fake_quantize_tree``,
``models.quantize.strum_serve_params``) remain as thin deprecated shims
over plan construction; the old ``models.quantize.gather_dequant`` shim is
gone — the registry's ``sharded:*`` family owns the compressed gather.
"""
import jax

# A device trace attributes ops by the scopes in their ``op_name``
# (``pallas:onehot``, ``attn:fused``, ``head:dense``, ...).  That is
# metadata, which JAX leaves out of the persistent compilation cache's key
# by default, so a hit would hand back the op_names of whichever build
# compiled the same ops first.  Keying on it keeps a program's names its own.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

from repro.engine.cache import (CacheSpec, build_cache_spec, decode_pages,
                                encode_page, gather_decode_pages,
                                select_cache_variant)
from repro.engine.dispatch import (apply, dequant_leaf, dispatch,
                                   dispatch_grouped, leaf_spec)
from repro.engine.draft import (DraftPolicy, build_draft_plan,
                                draft_dequant_leaf, draft_plan_bytes)
from repro.engine.plan import (ExecutionPlan, PlanEntry, build_plan,
                               fake_quantize)
from repro.engine.registry import (BACKENDS, ExecSpec, KernelVariant,
                                   LeafInfo, ShardSpec, get_variant,
                                   list_variants, register_kernel,
                                   resolve_backend, select_variant,
                                   unregister_kernel)
from repro.engine.sharded import (dense_gather_bytes,
                                  tp_pattern_for)

__all__ = [
    "apply", "dispatch", "dispatch_grouped", "dequant_leaf", "leaf_spec",
    "ExecutionPlan", "PlanEntry", "build_plan", "fake_quantize",
    "BACKENDS", "ExecSpec", "KernelVariant", "LeafInfo", "ShardSpec",
    "register_kernel", "unregister_kernel", "get_variant", "list_variants",
    "select_variant", "resolve_backend",
    "dense_gather_bytes", "tp_pattern_for",
    "CacheSpec", "build_cache_spec", "select_cache_variant",
    "encode_page", "decode_pages", "gather_decode_pages",
    "DraftPolicy", "build_draft_plan", "draft_dequant_leaf",
    "draft_plan_bytes",
]
