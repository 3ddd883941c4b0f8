"""Step builders: jit-able train / prefill / decode steps with shardings.

These are shared by the trainer, the server, and the dry-run — one
definition of each step so what we lower at 512 devices is exactly what we
run in tests.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.models import transformer as tfm
from repro.models.sharding import Rules, rules_for_mesh
from repro.optim import adamw
from repro.runtime import compression as gcomp

__all__ = ["make_train_step", "make_prefill_step", "make_decode_step",
           "make_paged_decode_step", "make_chunked_prefill_step",
           "make_verify_step", "build_serving_plan", "LANE_PROGRAMS"]

# The HLO module each serving lane compiles to: ``jit_`` and the name of the
# step function its builder returns.  The device trace's ``XLA Modules``
# line shows each lane's runs under this name (the KV-page sealer of
# ``serving.pages.make_sealer`` runs as ``jit_seal``).
LANE_PROGRAMS = {
    "decode": "jit_decode_step",                # make_paged_decode_step
    "prefill_chunk": "jit_prefill_chunk_step",  # make_chunked_prefill_step
    "prefill": "jit_prefill_step",              # make_prefill_step
    "verify": "jit_verify_step",                # make_verify_step
    "dense_decode": "jit_dense_decode_step",    # make_decode_step
}


def _rules(mesh, rules: Optional[Rules]) -> Optional[Rules]:
    """The caller's sharding rules, else the mesh's defaults, else none."""
    return rules or (rules_for_mesh(mesh) if mesh is not None else None)


def build_serving_plan(params, *, schedule=None, cfg=None, policy=None,
                       backend: Optional[str] = None, mesh=None,
                       rules: Optional[Rules] = None):
    """Serving-side plan construction with mesh context threaded through.

    The one place ``launch/serve``, ``serving.scheduler`` and callers of the
    step builders turn ``(params, schedule | cfg)`` into an
    :class:`repro.engine.ExecutionPlan`: with a ``mesh`` (and optional
    ``rules``) every entry records its distributed layout and selects from
    the registry's ``sharded:*`` family, so the same plan that serves one
    device serves the FSDP×TP mesh with compressed gathers.
    """
    from repro import engine
    return engine.build_plan(params, schedule=schedule, cfg=cfg,
                             policy=policy, backend=backend, mesh=mesh,
                             rules=_rules(mesh, rules))


def make_train_step(cfg, opt_cfg: adamw.AdamWConfig, mesh=None,
                    rules: Optional[Rules] = None,
                    grad_compression: bool = False):
    """(params, opt_state[, ef_state], batch) -> updated state + metrics."""
    rules = _rules(mesh, rules)

    def loss(params, batch):
        return tfm.loss_fn(params, batch, cfg, mesh=mesh, rules=rules)

    if grad_compression:
        def step(params, opt_state, ef_state, batch):
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
                params, batch)
            grads, ef_state = gcomp.compress_tree_with_ef(grads, ef_state)
            params, opt_state, stats = adamw.adamw_update(
                opt_cfg, params, grads, opt_state)
            metrics = dict(metrics, loss=l, **stats)
            return params, opt_state, ef_state, metrics
        return step

    def step(params, opt_state, batch):
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
            params, batch)
        params, opt_state, stats = adamw.adamw_update(
            opt_cfg, params, grads, opt_state)
        metrics = dict(metrics, loss=l, **stats)
        return params, opt_state, metrics
    return step


def make_prefill_step(cfg, mesh=None, rules: Optional[Rules] = None):
    rules = _rules(mesh, rules)

    def prefill_step(params, batch):
        return tfm.prefill(params, batch, cfg, mesh=mesh, rules=rules)
    return prefill_step


def make_decode_step(cfg, mesh=None, rules: Optional[Rules] = None):
    rules = _rules(mesh, rules)

    def dense_decode_step(params, token, caches, cache_len):
        return tfm.decode_step(params, token, caches, cache_len, cfg,
                               mesh=mesh, rules=rules)
    return dense_decode_step


def make_paged_decode_step(cfg, spec, mesh=None,
                           rules: Optional[Rules] = None,
                           cache_backend: Optional[str] = None):
    """Decode lane of the paged serving runtime: one (n_slots, 1) step over
    page-table caches.  ``spec`` (a :class:`repro.engine.cache.CacheSpec`)
    rides the closure as static codec metadata."""
    rules = _rules(mesh, rules)

    def decode_step(params, token, pools, hot, cache_len, page_table, active):
        return tfm.decode_step_paged(params, token, pools, hot, cache_len,
                                     page_table, active, spec, cfg,
                                     mesh=mesh, rules=rules,
                                     cache_backend=cache_backend)
    return decode_step


def make_chunked_prefill_step(cfg, spec, mesh=None,
                              rules: Optional[Rules] = None,
                              cache_backend: Optional[str] = None):
    """Prefill lane: one fixed-shape (1, chunk) step that any slot's prompt
    advances through — the single prefill executable that replaces the old
    compile-per-prompt-length path."""
    rules = _rules(mesh, rules)

    def prefill_chunk_step(params, tokens, pools, hot, page_table, slot,
                           start, valid_len):
        return tfm.prefill_chunk_step(params, tokens, pools, hot, page_table,
                                      slot, start, valid_len, spec, cfg,
                                      mesh=mesh, rules=rules,
                                      cache_backend=cache_backend)
    return prefill_chunk_step


def make_verify_step(cfg, spec, mesh=None, rules: Optional[Rules] = None,
                     cache_backend: Optional[str] = None):
    """Verify lane of self-speculative decoding: one fixed-shape (1, k+1)
    step that scores a slot's draft window at full fidelity without
    mutating any cache state — the scheduler commits accepted KV rows
    itself (its rollback)."""
    rules = _rules(mesh, rules)

    def verify_step(params, tokens, pools, hot, page_table, slot, start):
        return tfm.verify_chunk_step(params, tokens, pools, hot, page_table,
                                     slot, start, spec, cfg, mesh=mesh,
                                     rules=rules,
                                     cache_backend=cache_backend)
    return verify_step
