"""The representative verification suite behind ``python -m repro.analysis``.

Builds small-but-real instances of every jitted entry point the engine
serves — local ``engine.apply`` dispatch, each registered ``sharded:*``
variant under col/row TP layouts, the ``cache:*`` page codecs, and the
scheduler's serving lanes — and runs the four analysis passes over them:

* packed-dataflow verification (:mod:`repro.analysis.dataflow`),
* registry audit (:mod:`repro.analysis.registry_audit`),
* Pallas kernel lint (:mod:`repro.analysis.pallas_lint`),
* recompile lint (:mod:`repro.analysis.recompile`),
* numerics abstract interpretation (:mod:`repro.analysis.numerics`),
  including its soundness self-check: the statically derived output-error
  bound must dominate the measured teacher-forced error, or the suite
  reports ``numerics/unsound-bound``.

Everything except the recompile pass is trace-only.  The sharded scenarios
prove the Eq.-1 collective-byte invariant statically for *every* variant in
the ``sharded:*`` family, on whatever device count is available — a
1-device mesh traces the same ``all_gather`` equations with
``axis_size=1``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import dataflow, pallas_lint, recompile, registry_audit
from repro.analysis.report import Report
from repro.core.policy import StruMConfig
from repro.launch.mesh import make_mesh

__all__ = ["PASSES", "run_all", "tiny_model", "verify_local_apply",
           "verify_sharded_variants", "verify_cache_codecs",
           "verify_scheduler_lanes", "verify_fused_attention",
           "verify_numerics", "verify_draft_payload", "check_cache_pools"]

PASSES = ("dataflow", "registry", "pallas", "recompile", "numerics",
          "draft")

_WCFG = StruMConfig(method="mip2q", w=16, p=0.5, L=5)
_KVCFG = StruMConfig(method="dliq", w=16, p=0.5, q=4)
_KVCFG_MIP = StruMConfig(method="mip2q", w=16, p=0.5, L=7)


def tiny_model(arch: str = "qwen2_7b"):
    """(ModelConfig, float32 params) for a smoke-scale architecture."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import model_defs
    from repro.models.params import init_params

    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = init_params(model_defs(cfg), seed=0, dtype_override="float32")
    return cfg, params


def _payload_bytes(wleaf: dict) -> int:
    return int(sum(wleaf[k].size for k in ("mask", "hi", "lo")))


def _leaf(k: int, n: int, cfg: StruMConfig, lead: tuple = ()) -> dict:
    from repro.models.quantize import _pack_leaf

    return _pack_leaf(np.zeros(lead + (k, n), np.float32), cfg)


# ------------------------------------------------------------- scenarios --

def verify_local_apply(backend: Optional[str] = "interpret") -> Report:
    """Single-device dispatch: decode-exactly-once, no collectives."""
    from repro.engine.dispatch import dispatch

    report = Report()
    k, n = 64, 128
    for cfg, label in ((_WCFG, "mip2q"), (_KVCFG, "dliq"),
                       (StruMConfig(method="sparsity", w=16, p=0.5),
                        "sparsity")):
        wleaf = _leaf(k, n, cfg)
        report.extend(dataflow.verify(
            lambda lf, x, _cfg=cfg: dispatch(lf, x, strum=_cfg,
                                             backend=backend),
            wleaf, jax.ShapeDtypeStruct((4, k), jnp.float32),
            location=f"engine.apply[{label}]"))
    return report


def _mesh_2d():
    n = len(jax.devices())
    if n >= 4:
        return make_mesh((2, 2), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))


def _mesh_1d():
    n = len(jax.devices())
    return make_mesh((2 if n >= 2 else 1,), ("data",))


def verify_sharded_variants(cfg: StruMConfig = _WCFG) -> Report:
    """Statically prove the Eq.-1 gather invariant for every registered
    ``sharded:*`` variant (packed-only collectives, decode-once, global
    gathered bytes == mask+hi+lo == K x N x compression_ratio)."""
    from jax.sharding import PartitionSpec as P

    from repro.engine.registry import list_variants
    from repro.models.sharding import shard_map

    report = Report()
    k, n = 128, 256

    for name, variant in sorted(list_variants().items()):
        if not variant.sharded:
            continue
        if variant.grouped:
            mesh = _mesh_1d()
            fsdp = ("data",)
            lead = (2,)
            wleaf = _leaf(k, n, cfg, lead=lead)
            x = jnp.zeros(lead + (4, k), jnp.float32)
            pay_spec = P(None, fsdp, None, None)
            leaf_specs = {"mask": pay_spec, "hi": pay_spec, "lo": pay_spec,
                          "scale": P(None, None, None)}

            def run(lf, xg, _v=variant, _fsdp=fsdp):
                return _v.fn(lf, xg, cfg=cfg, mesh=None, fsdp=_fsdp,
                             pattern=None, k_dim=k, backend="interpret",
                             interpret=True, accum_dtype=jnp.float32,
                             out_dtype=jnp.float32)

            fn = shard_map(
                run, mesh=mesh, in_specs=(leaf_specs, P(None, None, None)),
                out_specs=P(None, None, None), check_vma=False)
            report.extend(dataflow.verify(
                fn, wleaf, x, location=name, mesh=mesh,
                expected_payload_bytes=_payload_bytes(wleaf),
                cfg=cfg, k_dim=k, n_out=n * lead[0]))
            continue

        mesh = _mesh_2d()
        fsdp = ("data",)
        wleaf = _leaf(k, n, cfg)
        backend = "interpret" if variant.family == "pallas" else None
        for pattern in ("col", "row"):
            fn = functools.partial(
                variant.fn, cfg=cfg, mesh=mesh, fsdp=fsdp, pattern=pattern,
                k_dim=k, backend=backend, interpret=True,
                accum_dtype=jnp.float32, out_dtype=jnp.float32)
            report.extend(dataflow.verify(
                fn, wleaf, jnp.zeros((4, k), jnp.float32),
                location=f"{name}[{pattern}]", mesh=mesh,
                expected_payload_bytes=_payload_bytes(wleaf),
                cfg=cfg, k_dim=k, n_out=n))
    return report


def verify_cache_codecs(kv: StruMConfig = _KVCFG) -> Report:
    """Packed page pools: decode-once, no fp payload fields, and payload
    bytes at the Eq.-1 page ratio."""
    from repro.engine import cache as cache_mod

    report = Report()
    page, feat, n_pages = 64, 32, 8
    for backend in (None, "interpret"):
        spec = cache_mod.build_cache_spec(kv, page_size=page, feat=feat,
                                          backend=backend)
        structs = jax.eval_shape(
            functools.partial(cache_mod.encode_page, cfg=kv),
            jax.ShapeDtypeStruct((page, feat), jnp.float32))
        pool = {f: jax.ShapeDtypeStruct((n_pages,) + tuple(s.shape), s.dtype)
                for f, s in structs.items()}
        loc = f"cache.gather_decode_pages[{spec.variant}]"
        for f in ("mask", "hi", "lo"):
            if np.issubdtype(np.dtype(pool[f].dtype), np.floating):
                report.add("error", "cache/fp-page", f"{loc}/{f}",
                           f"payload pool field is {pool[f].dtype}")
        report.extend(dataflow.verify(
            lambda p, ids, _s=spec, _b=backend: cache_mod.gather_decode_pages(
                p, ids, _s, backend=_b),
            pool, jax.ShapeDtypeStruct((2, 3), jnp.int32), location=loc))
        want = cache_mod.page_payload_bytes(page, feat, kv)
        got = sum(int(np.prod(pool[f].shape)) // n_pages
                  for f in ("mask", "hi", "lo"))
        if got != want:
            report.add("error", "dataflow/eq1-bytes", loc,
                       f"page payload {got} B != page_payload_bytes {want}")
    return report


def check_cache_pools(pools: dict, spec, location: str) -> Report:
    """No fp bytes inside sealed packed pages (the pool-side static check)."""
    from jax.tree_util import keystr, tree_leaves_with_path

    report = Report()
    if not getattr(spec, "packed", False):
        return report
    for path, arr in tree_leaves_with_path(pools):
        field = getattr(path[-1], "key", str(path[-1]))
        if field == "scale":
            continue
        if np.issubdtype(np.dtype(arr.dtype), np.floating):
            report.add("error", "cache/fp-page",
                       f"{location}{keystr(path)}",
                       f"packed pool stores {arr.dtype} — fp bytes leak "
                       f"out of sealed pages")
    return report


def build_tiny_scheduler(cfg, params, *, kv=_KVCFG, wcfg=_WCFG,
                         n_slots: int = 2, max_len: int = 48,
                         cache_backend=None, speculative: int = 0,
                         draft=None):
    """A packed-weights, packed-KV scheduler for lane analysis."""
    from repro import engine
    from repro.serving import BatchScheduler

    plan = engine.build_plan(params, cfg=wcfg, float_only=True)
    return BatchScheduler(cfg, params, n_slots=n_slots, max_len=max_len,
                          plan=plan, kv_cache=kv, page_size=kv.w,
                          cache_backend=cache_backend,
                          speculative=speculative, draft=draft)


def verify_scheduler_lanes(sched, location: str = "scheduler") -> Report:
    """Trace both serving lanes (no execution) and run the dataflow pass:
    weights and sealed pages decode exactly once, nothing gathers fp."""
    report = check_cache_pools(sched.pools, sched.spec,
                               f"{location}/pools")
    ns, pps = sched.n_slots, sched.pages_per_seq
    table = jnp.zeros((ns, pps), jnp.int32)
    report.extend(dataflow.verify(
        sched._decode, sched.params,
        jnp.zeros((ns, 1), jnp.int32), sched.pools, sched.hot,
        jnp.zeros((ns,), jnp.int32), table,
        jnp.ones((ns,), bool), location=f"{location}/decode-lane"))
    report.extend(dataflow.verify(
        sched._chunk_prefill, sched.params,
        jnp.zeros((1, sched.prefill_chunk), jnp.int32), sched.pools,
        sched.hot, table, jnp.int32(0), jnp.int32(0), jnp.int32(1),
        location=f"{location}/prefill-lane"))
    return report


def verify_fused_attention(arch: str = "qwen2_7b", model=None) -> Report:
    """The Eq.-1 HBM gate for the fused decode lane.

    For packed q=4 codecs (DLIQ and MIP2Q) under a pallas-family backend
    the scheduler must select ``cache:attn_fused``, and the traced decode
    step's gather-class reads of the sealed pools must materialize exactly
    the mask+hi+lo payload: no raw fp page bytes, no post-decode re-gather
    (``dataflow/fp-page``), each pool decoded exactly once.  Byte counts
    are per traced step — the layer-group scan body counts once, which is
    exactly the per-executable granularity the telemetry counters use.
    """
    from repro.engine import cache as cache_mod
    from repro.serving import pages as pages_mod

    report = Report()
    cfg, params = model or tiny_model(arch)
    feat = pages_mod.attn_feat_dim(cfg)
    for kv, label in ((_KVCFG, "dliq_q4"), (_KVCFG_MIP, "mip2q_L7")):
        sched = build_tiny_scheduler(cfg, params, kv=kv,
                                     cache_backend="interpret")
        loc = f"{arch}/attn-fused[{label}]"
        if sched.spec.attn_variant != "cache:attn_fused":
            report.add("error", "attn/unfused-lane", loc,
                       f"packed codec {kv.method} w={kv.w} q={kv.q} selected "
                       f"{sched.spec.attn_variant!r}")
            continue
        ns, pps = sched.n_slots, sched.pages_per_seq
        ppb = cache_mod.page_payload_bytes(sched.spec.page_size, feat, kv)
        n_pools = sum(1 for v in sched.pools.values() if v)
        table = jnp.zeros((ns, pps), jnp.int32)
        report.extend(dataflow.verify(
            sched._decode, sched.params,
            jnp.zeros((ns, 1), jnp.int32), sched.pools, sched.hot,
            jnp.zeros((ns,), jnp.int32), table,
            jnp.ones((ns,), bool), location=f"{loc}/decode-lane",
            expected_gather_packed_bytes=n_pools * 2 * ns * pps * ppb,
            forbid_fp_pages=True))
    return report


def _live_invars(jaxpr) -> set:
    """Indices of ``jaxpr.invars`` that can reach computation or an output.

    An invar is *live* iff it feeds some equation (recursively: feeding a
    position a scan/pjit sub-jaxpr itself treats as dead does not count —
    positional alignment of eqn invars to sub-jaxpr invars holds exactly
    when the lengths match, which covers scan's ``consts ++ carry ++ xs``
    layout) or is returned directly.  Packed payload streams a draft
    variant skips must come out dead: the kernel never names them, so the
    buffers never leave HBM.
    """
    idx = {id(v): i for i, v in enumerate(jaxpr.invars)}
    live: set = set()
    for eqn in jaxpr.eqns:
        subs = list(dataflow._sub_jaxprs(eqn.params))
        if len(subs) == 1 and len(subs[0].invars) == len(eqn.invars):
            sub_live = _live_invars(subs[0])
            for pos, v in enumerate(eqn.invars):
                if id(v) in idx and pos in sub_live:
                    live.add(idx[id(v)])
        else:
            for v in eqn.invars:
                if id(v) in idx:
                    live.add(idx[id(v)])
    for v in jaxpr.outvars:
        if id(v) in idx:
            live.add(idx[id(v)])
    return live


def verify_draft_payload(sched, location: str = "scheduler") -> Report:
    """Static proof that the draft lane reads a strict byte-subset of the
    target payload — speculative decoding's "free draft model" claim.

    Three checks on a ``speculative=k`` scheduler, all trace-time:

    1. ``draft/extra-bytes`` — every packed leaf of the draft plan must
       hold the *same* mask/hi/lo/scale buffers (by identity) as the
       target plan: zero additional weight bytes resident in HBM.
    2. ``draft/stream-read`` — in the jaxpr of the (unjitted) draft
       decode step, each stream a leaf's draft mode declares skipped
       (``histream`` → lo; ``maskfree_p`` → mask+lo) must be a dead
       input: never fed to any equation, so it is never read.
    3. ``draft/no-subset`` — summing live payload bytes over all packed
       leaves must land strictly below the full payload AND agree with
       ``draft_plan_bytes``'s declared draft bytes.
    """
    from jax.tree_util import tree_leaves, tree_map_with_path

    from repro.core.apply import path_name
    from repro.engine.draft import (_is_packed_leaf, draft_field_set,
                                    draft_plan_bytes)
    from repro.launch.steps import make_paged_decode_step

    report = Report()
    if not getattr(sched, "speculative", 0):
        report.add("error", "draft/no-subset", location,
                   "scheduler has no draft lane (speculative=0); nothing "
                   "to prove")
        return report
    modes = sched.draft_plan.meta.get("draft", {})

    def collect(tree):
        leaves: dict = {}

        def visit(path, leaf):
            if _is_packed_leaf(leaf):
                leaves[path_name(path)] = leaf
            return leaf
        tree_map_with_path(visit, tree, is_leaf=_is_packed_leaf)
        return leaves

    target = collect(sched.plan.params)
    drafted = collect(sched._draft_params)
    for name, dleaf in sorted(drafted.items()):
        tleaf = target.get(name)
        for f in ("mask", "hi", "lo", "scale"):
            if tleaf is None or dleaf[f] is not tleaf[f]:
                report.add("error", "draft/extra-bytes",
                           f"{location}/{name}/{f}",
                           "draft plan does not share the target plan's "
                           "payload buffer — the draft would cost extra "
                           "HBM residency")

    step = make_paged_decode_step(sched.cfg, sched.spec)
    ns, pps = sched.n_slots, sched.pages_per_seq
    args = (sched._draft_params, jnp.zeros((ns, 1), jnp.int32), sched.pools,
            sched.hot, jnp.zeros((ns,), jnp.int32),
            jnp.zeros((ns, pps), jnp.int32), jnp.ones((ns,), bool))
    closed = jax.make_jaxpr(step)(*args)
    flat = tree_leaves(args)
    assert len(flat) == len(closed.jaxpr.invars), \
        (len(flat), len(closed.jaxpr.invars))
    pos_of = {id(a): i for i, a in enumerate(flat)}
    live = _live_invars(closed.jaxpr)

    live_bytes = full_bytes = 0
    for name, dleaf in sorted(drafted.items()):
        mode = modes.get(name, "")
        streamed = set(draft_field_set(mode)) if mode else \
            {"mask", "hi", "lo"}
        for f in ("mask", "hi", "lo"):
            i = pos_of.get(id(dleaf[f]))
            is_live = i is not None and i in live
            full_bytes += int(dleaf[f].size)
            if is_live:
                live_bytes += int(dleaf[f].size)
            if mode and f not in streamed and is_live:
                report.add("error", "draft/stream-read",
                           f"{location}/{name}/{f}",
                           f"draft mode {mode!r} declares the {f} stream "
                           f"skipped, but the traced draft decode step "
                           f"reads it")

    decl = draft_plan_bytes(sched.draft_plan)
    if not any(modes.values()) or live_bytes >= full_bytes:
        report.add("error", "draft/no-subset", location,
                   f"draft lane live payload {live_bytes} B is not a "
                   f"strict subset of the full payload {full_bytes} B")
    elif live_bytes != decl["draft_bytes"]:
        report.add("error", "draft/no-subset", location,
                   f"traced live payload {live_bytes} B != declared draft "
                   f"bytes {decl['draft_bytes']} B "
                   f"(draft_plan_bytes drifted from the traced truth)")
    return report


_NUMERICS_CFGS = (StruMConfig(method="dliq", w=8, p=0.5, q=4),
                  StruMConfig(method="mip2q", w=8, p=0.5, L=3))


def verify_numerics(arch: str = "qwen2_7b",
                    cfgs=_NUMERICS_CFGS) -> Report:
    """Numerics pass + soundness self-check on a real packed forward.

    For each schedule: derive the static per-layer and end-to-end
    output-error bound with :func:`repro.analysis.numerics.analyze`, then
    run the float and the packed forward teacher-forced on the same tokens
    and require ``static bound >= measured error`` — a violated inequality
    is a bug in the interpreter itself and reports
    ``numerics/unsound-bound``.  Schedules that declare an error budget
    (``Budget(error_budget=...)`` via autotune) are additionally checked
    with :func:`repro.analysis.numerics.check_error_budget`.
    """
    from repro import engine
    from repro.analysis import numerics
    from repro.models.transformer import forward_train

    cfg, params = tiny_model(arch)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0,
                              cfg.vocab_size)

    def fn(p, t):
        return forward_train(p, {"tokens": t}, cfg)[0]

    report = Report()
    for scfg in cfgs:
        loc = f"{arch}/numerics[{scfg.method} w={scfg.w} p={scfg.p}]"
        plan = engine.build_plan(params, cfg=scfg, backend="xla", pack=True)
        stats = numerics.leaf_stats_from_plan(plan, params)
        res, rep = numerics.analyze(fn, plan.params, toks, stats=stats,
                                    location=loc)
        report.extend(rep)
        measured = numerics.measured_error(fn, (params, toks),
                                           (plan.params, toks))
        if res.total < measured:
            report.add("error", "numerics/unsound-bound", loc,
                       f"static bound {res.total:.6g} < measured "
                       f"teacher-forced error {measured:.6g}")
        budget = _schedule_error_budget(plan.schedule)
        if budget is not None:
            report.extend(numerics.check_error_budget(
                res, {"total": budget}, location=loc))
    return report


def _schedule_error_budget(schedule):
    meta = getattr(schedule, "meta", None) or {}
    return (meta.get("budget") or {}).get("error_budget")


# --------------------------------------------------------------- runner --

def run_all(arches=("qwen2_7b",), passes=PASSES,
            lint_cfgs: Optional[list] = None):
    """Run the requested passes; returns ``(Report, AuditData | None)``."""
    report = Report()
    audit_data = None
    if "registry" in passes:
        r, audit_data = registry_audit.audit_registry()
        report.extend(r)
    if "pallas" in passes:
        report.extend(pallas_lint.lint_pallas(cfgs=lint_cfgs))
    if "dataflow" in passes:
        report.extend(verify_local_apply())
        report.extend(verify_sharded_variants())
        report.extend(verify_cache_codecs())
    if "numerics" in passes:
        for arch in arches:
            report.extend(verify_numerics(arch))
    if "dataflow" in passes or "recompile" in passes:
        for arch in arches:
            cfg, params = tiny_model(arch)
            sched = build_tiny_scheduler(cfg, params)
            if "dataflow" in passes:
                report.extend(verify_scheduler_lanes(
                    sched, location=f"{arch}/scheduler"))
                report.extend(verify_fused_attention(
                    arch, model=(cfg, params)))
            if "recompile" in passes:
                report.extend(recompile.lint_scheduler_recompiles(
                    sched=sched, location=f"{arch}/scheduler"))
    if "draft" in passes:
        for arch in arches:
            cfg, params = tiny_model(arch)
            for mode in ("histream", "maskfree_p"):
                sched = build_tiny_scheduler(cfg, params, speculative=2,
                                             draft=mode)
                report.extend(verify_draft_payload(
                    sched, location=f"{arch}/draft[{mode}]"))
            if "recompile" in passes:
                # the speculative lanes (draft decode / verify / commit)
                # must hold the one-executable invariant too
                sched = build_tiny_scheduler(cfg, params, speculative=2)
                report.extend(recompile.lint_scheduler_recompiles(
                    sched=sched, location=f"{arch}/spec-scheduler"))
    return report, audit_data
