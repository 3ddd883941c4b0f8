"""Recompile lint: prove the serving lanes stay fixed-shape.

PR 5's scheduler rebuild hinges on one invariant: every lane is ONE
compiled executable — decode serves every slot mix, chunked prefill serves
every prompt length (``slot``/``start``/``valid_len`` are traced scalars).
A change that turns any of those into a static Python value silently
reintroduces the compile-per-prompt-length storm.

This pass runs a deliberately shape-diverse tiny workload (mixed prompt
lengths, more requests than slots) through a :class:`BatchScheduler` and
then reads each lane's jit cache size — more traces than a lane may hold
(one; the sealer one per source shape) is ``recompile/lane-retrace``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.analysis.report import Report

__all__ = ["lint_scheduler_recompiles", "lane_trace_counts"]

#: prompt lengths chosen to straddle page and chunk boundaries
DEFAULT_PROMPT_LENS = (3, 7, 16, 21, 33)

#: executables a lane may hold; one unless listed.  The sealer cuts its page
#: out of its source inside the program, so it compiles once per source
#: shape: the hot tail tree and the prefill chunk's KV window.
LANE_EXECUTABLES = {"seal": 2}


def _cache_size(jitted) -> Optional[int]:
    fn = getattr(jitted, "_cache_size", None)
    return int(fn()) if callable(fn) else None


def lane_trace_counts(sched) -> dict:
    """Compiled-trace count per lane executable of a scheduler."""
    lanes = {
        "decode": sched._decode,
        "chunk_prefill": sched._chunk_prefill,
        "serial_prefill": sched._prefill,
        "seal": sched._seal,
    }
    if getattr(sched, "speculative", 0):
        lanes["draft_decode"] = sched._draft_decode
        lanes["verify"] = sched._verify
        lanes["commit"] = sched._commit
    return {name: _cache_size(fn) for name, fn in lanes.items()
            if _cache_size(fn) is not None}


def lint_scheduler_recompiles(sched=None, *, cfg=None, params=None,
                              prompt_lens=DEFAULT_PROMPT_LENS,
                              max_new_tokens: int = 4,
                              location: str = "scheduler",
                              **sched_kwargs) -> Report:
    """Drive a mixed-length workload and flag any lane that retraced.

    Pass a prebuilt ``sched`` (it will be *run*), or ``cfg``/``params`` to
    build a small one (2 slots, chunked prefill) here.
    """
    from repro.serving import BatchScheduler, Request

    if sched is None:
        if cfg is None or params is None:
            raise ValueError("need sched= or cfg=/params=")
        sched = BatchScheduler(cfg, params, n_slots=2,
                               max_len=max(prompt_lens) + max_new_tokens + 8,
                               **sched_kwargs)
    rng = np.random.default_rng(0)
    vocab = int(sched.cfg.vocab_size)
    for i, plen in enumerate(prompt_lens):
        prompt = jnp.asarray(rng.integers(0, vocab, size=(plen,)), jnp.int32)
        sched.submit(Request(uid=i, prompt=prompt,
                             max_new_tokens=max_new_tokens))
    sched.run_to_completion(max_steps=64 * len(prompt_lens))

    report = Report()
    for lane, count in lane_trace_counts(sched).items():
        limit = LANE_EXECUTABLES.get(lane, 1)
        if count > limit:
            report.add(
                "error", "recompile/lane-retrace", f"{location}/{lane}",
                f"lane compiled {count} executables across prompt lengths "
                f"{tuple(prompt_lens)}; the fixed-shape invariant allows "
                f"{limit}")
    return report
