"""Continuous-batching scheduler over paged, StruM-compressible KV caches.

The serving runtime: a priority request queue, slot-based batching, a page
allocator, and two fixed-shape lanes —

  * **decode lane** — one compiled step for (n_slots, 1): every decoding
    slot advances one token per tick; parked / mid-prefill slots ride the
    batch masked (their hot state is protected by an ``active`` mask).
  * **prefill lane** — one compiled step for (1, prefill_chunk): every
    prompt of every slot runs through the same executable, chunk by chunk,
    with ``slot``/``start``/``valid_len`` as traced scalars.  This replaces
    the old compile-per-prompt-length prefill, so the no-recompile-storm
    invariant now covers prefill too; ``prefill="serial"`` keeps the
    monolithic one-shot prefill (and charges the decode lane the
    head-of-line stall the monolithic executable implies) as the
    comparison baseline ``benchmarks/serving_bench.py`` measures against.

Cache storage is a page table (:mod:`repro.serving.pages`): fixed-size
pages, allocated at admission, sealed — optionally *packed* through the
engine's ``cache:*`` codec family (``kv_cache=StruMConfig(...)``) — when
they fill, and freed (allocator defrag) at retirement.  With a packed codec
the resident cache sits at the paper's Eq.-1/2 ratio and decode reads
stream packed pages through the registry-selected decoder
(``cache:pallas_decode`` / ``cache:xla_dequant``), mirroring what the
weight path already does; ``kv_cache=None`` stores raw fp pages
(``cache:fp_passthrough``) and is value-identical to the old monolithic
cache.

Weights compress exactly as before: ``plan=`` (a prebuilt
:class:`repro.engine.ExecutionPlan`) or ``schedule=`` (+ ``backend=``,
``mesh=``/``rules=``) — the deployment end of the
profile → search → schedule → plan → serve flow.

CPU-scale but structurally the real thing; exercised by
tests/test_scheduler.py, tests/test_serving_runtime.py and
examples/serve_batch.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.engine.cache import pages_gathered
from repro.launch.steps import (make_chunked_prefill_step,
                                make_paged_decode_step, make_prefill_step,
                                make_verify_step)
from repro.serving import pages as pages_mod
from repro.serving.pages import PageAllocator, PagesExhausted

__all__ = ["Request", "BatchScheduler", "PagesExhausted"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: jnp.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    priority: int = 0              # higher admits first (FIFO within a tier)
    # teacher forcing: feed these tokens back instead of the argmax — the
    # scheduler still *records* its own predictions in ``output``, so two
    # runtimes can be compared per-position on an identical trajectory
    force_tokens: Optional[list] = None
    # filled by the scheduler:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False

    def _feed(self, k: int, predicted: int) -> int:
        if self.force_tokens is not None and k < len(self.force_tokens):
            return int(self.force_tokens[k])
        return predicted


@dataclasses.dataclass
class _Slot:
    req: Request
    pages: list                    # reserved page ids (sealed in order)
    len: int = 0                   # committed cache positions
    n_sealed: int = 0
    state: str = "prefill"         # "prefill" -> "decode"
    pf_start: int = 0              # next chunk's absolute start position


class BatchScheduler:
    """n_slots-way continuous batching over paged caches.

    Cache knobs: ``kv_cache`` (None/"fp" for raw pages, or a
    :class:`repro.core.policy.StruMConfig` — e.g.
    ``StruMConfig(method="dliq", q=4)`` — to store sealed pages packed),
    ``page_size`` (must be a multiple of the codec's block width ``w``),
    ``n_pages`` (pool size; default fits every slot's full window),
    ``cache_backend`` (pins the ``cache:*`` decoder selection, same strings
    as the weight engine's ``backend=``).

    Prefill knobs: ``prefill="chunked"`` (default — chunks of
    ``prefill_chunk`` tokens interleave with the decode lane, one chunk per
    tick) or ``"serial"`` (monolithic prefill; the decode lane stalls
    ``ceil(prompt/chunk)`` ticks — the head-of-line blocking the chunked
    lane exists to remove).

    Weight knobs are unchanged from the monolithic scheduler: ``plan=`` /
    ``schedule=`` / ``backend=`` / ``mesh=`` / ``rules=``.

    Speculative knobs: ``speculative=k`` (k > 0) turns the decode lane into
    a draft/verify round — up to ``k`` draft tokens per slot per tick from
    the *same* packed payload read at reduced fidelity
    (:func:`repro.engine.build_draft_plan`; ``draft=`` picks the mode or a
    full :class:`repro.engine.DraftPolicy`), then one fixed-shape
    ``(1, k+1)`` full-fidelity verify step scores the window and the
    longest accepted prefix commits.  Greedy output is token-identical to
    plain decode; rejected KV never commits (the verify lane mutates
    nothing, accepted rows are written back explicitly).  Attention-only
    stacks — SSM state cannot roll back.
    """

    def __init__(self, cfg, params, n_slots: int = 4, max_len: int = 256,
                 mesh=None, rules=None, schedule=None, plan=None,
                 backend=None, kv_cache=None, page_size: int = 16,
                 n_pages: Optional[int] = None, prefill: str = "chunked",
                 prefill_chunk: Optional[int] = None, cache_backend=None,
                 speculative: int = 0, draft=None):
        if plan is not None and schedule is not None:
            raise ValueError("pass plan= or schedule=, not both")
        if plan is not None and backend is not None:
            raise ValueError("backend= only applies when the scheduler "
                             "builds the plan (schedule=...); a prebuilt "
                             "plan already recorded its variant selection")
        if prefill not in ("chunked", "serial"):
            raise ValueError(f"prefill={prefill!r}; want 'chunked'|'serial'")
        if schedule is not None:
            from repro.autotune.schedule import StruMSchedule
            from repro.launch.steps import build_serving_plan
            if isinstance(schedule, (str, bytes)) or hasattr(schedule, "__fspath__"):
                schedule = StruMSchedule.load(schedule)
            plan = build_serving_plan(params, schedule=schedule,
                                      backend=backend, mesh=mesh,
                                      rules=rules)
        if plan is not None:
            params = plan.params
            schedule = schedule if schedule is not None else plan.schedule
        self.plan = plan
        self.schedule = schedule
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len

        # ---- paged cache geometry -------------------------------------
        self.spec = pages_mod.make_cache_spec(cfg, kv_cache, page_size,
                                              backend=cache_backend)
        ps = self.spec.page_size
        self.page_size = ps
        self.pages_per_seq = pages_mod.pages_per_seq(max_len, ps)
        self.prefill_mode = prefill
        self.prefill_chunk = prefill_chunk or ps
        if self.prefill_chunk % ps:
            raise ValueError(f"prefill_chunk={self.prefill_chunk} must be a "
                             f"multiple of page_size={ps}")
        if (self.pages_per_seq * ps) % self.prefill_chunk:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must divide the padded "
                f"window {self.pages_per_seq * ps} "
                f"(= pages_per_seq * page_size)")
        self.n_pages = n_pages or n_slots * self.pages_per_seq
        self.allocator = PageAllocator(self.n_pages)
        self.pools = pages_mod.init_pools(cfg, self.n_pages, self.spec)
        self.hot = pages_mod.init_hot(cfg, n_slots, ps)
        self._seal = pages_mod.make_sealer(self.spec)
        self._attn_pos = [k for k, v in self.pools.items() if v]

        # ---- lanes -----------------------------------------------------
        self._decode = jax.jit(make_paged_decode_step(
            cfg, self.spec, mesh, rules, cache_backend=cache_backend))
        self._chunk_prefill = jax.jit(make_chunked_prefill_step(
            cfg, self.spec, mesh, rules, cache_backend=cache_backend))
        self._prefill = jax.jit(make_prefill_step(cfg, mesh, rules))

        # ---- speculative lanes ----------------------------------------
        self.speculative = int(speculative)
        self.draft_plan = None
        self.draft_policy = None
        self._draft_decode = self._verify = self._commit = None
        if self.speculative:
            from repro import engine
            if plan is None:
                raise ValueError(
                    "speculative=k needs a weight plan (plan= or schedule=):"
                    " the draft model is the plan's packed payload read at "
                    "reduced fidelity")
            if any(cfg.layer_kind(i) != "attn" for i in range(cfg.n_layers)):
                raise ValueError(
                    "speculative decoding needs an attention-only stack: "
                    "SSM recurrent state cannot roll back a rejected window")
            pol = (draft if isinstance(draft, engine.DraftPolicy)
                   else engine.DraftPolicy(mode=draft or "histream"))
            self.draft_policy = pol
            self.draft_plan = engine.build_draft_plan(plan, pol)
            self._draft_params = self.draft_plan.params
            self._draft_decode = jax.jit(make_paged_decode_step(
                cfg, self.spec, mesh, rules, cache_backend=cache_backend))
            self._verify = jax.jit(make_verify_step(
                cfg, self.spec, mesh, rules, cache_backend=cache_backend))
            self._commit = jax.jit(self._make_commit(ps))

        # ---- queue / slots --------------------------------------------
        self.queue: list[Request] = []
        self._seq = 0
        self._order: dict[int, int] = {}   # id(req) -> arrival index
        self.slots: list[Optional[_Slot]] = [None] * n_slots
        self._tokens = np.zeros((n_slots,), np.int64)
        self._table = np.full((n_slots, self.pages_per_seq), -1, np.int32)
        self._finished: list[Request] = []
        self._steps = 0
        self._stall = 0                    # serial-mode head-of-line ticks

    # ------------------------------------------------------------- intake --
    def submit(self, req: Request) -> None:
        """Validate + enqueue.  Impossible requests fail HERE, where the
        caller can handle them — not mid-run from inside step()."""
        plen = int(req.prompt.shape[0])
        if req.max_new_tokens > 0 and plen > self.max_len - 3:
            telemetry.inc("sched/reject/prompt_too_long")
            raise ValueError(
                f"request {req.uid}: prompt length {plen} does not fit the "
                f"serving window (max_len={self.max_len} leaves room for "
                f"{self.max_len - 3} prompt + 1 decode positions)")
        if self._pages_needed(req) > self.allocator.n_pages:
            telemetry.inc("sched/reject/pages_never_fit")
            raise PagesExhausted(
                f"request {req.uid}: needs {self._pages_needed(req)} pages "
                f"but the pool only holds {self.allocator.n_pages} — no "
                f"amount of retirement can admit it (raise n_pages=)")
        self._order[id(req)] = self._seq
        self._seq += 1
        self.queue.append(req)
        if telemetry.enabled():
            telemetry.inc("sched/submitted")
            telemetry.request_event(req.uid, "submitted", prompt_len=plen,
                                    max_new_tokens=req.max_new_tokens,
                                    priority=req.priority)
            telemetry.gauge("sched/queue_depth", len(self.queue))

    def _pages_needed(self, req: Request) -> int:
        plen = int(req.prompt.shape[0])
        return min(self.pages_per_seq,
                   -(-(plen + req.max_new_tokens) // self.page_size))

    def _admit(self) -> None:
        while self.queue:
            free = [s for s in range(self.n_slots) if self.slots[s] is None]
            if not free:
                telemetry.inc("sched/admit_wait/no_slot")
                return
            nxt = max(self.queue,
                      key=lambda r: (r.priority, -self._order[id(r)]))
            if nxt.max_new_tokens <= 0:
                # nothing to generate: complete at admission
                self.queue.remove(nxt)
                self._order.pop(id(nxt), None)
                nxt.done = True
                self._finished.append(nxt)
                if telemetry.enabled():
                    telemetry.inc("sched/retired")
                    telemetry.request_event(nxt.uid, "retired", n_tokens=0)
                    telemetry.gauge("sched/queue_depth", len(self.queue))
                continue
            if self.allocator.available < self._pages_needed(nxt):
                telemetry.inc("sched/admit_wait/no_pages")
                return                      # wait for retirements
            self.queue.remove(nxt)
            self._order.pop(id(nxt), None)
            slot = free[0]
            self.slots[slot] = _Slot(req=nxt,
                                     pages=self.allocator.alloc(
                                         self._pages_needed(nxt)))
            self._table[slot] = -1
            if telemetry.enabled():
                telemetry.inc("sched/admitted")
                telemetry.request_event(
                    nxt.uid, "admitted", slot=slot,
                    pages=len(self.slots[slot].pages))
                telemetry.gauge("sched/queue_depth", len(self.queue))
            if self.prefill_mode == "serial":
                self._serial_prefill(slot)

    # ------------------------------------------------------------ sealing --
    def _seal_into(self, slot: int, page_idx: int, srcs: dict, row: int,
                   start: int) -> None:
        """Write one full page per attention position into the pools.

        ``srcs[pos]`` is ``(k_src, v_src)`` of shape (g, B, T, KV, hd); the
        page is ``[:, row, start:start + page_size]`` of each, cut inside the
        sealer.  The sealer consumes the pool it is given (donated), so the
        pool is rebound to its result and nothing else may hold it.
        """
        sl = self.slots[slot]
        pid = sl.pages[page_idx]
        at = np.array([row, start, pid], np.int32)
        in_place = True
        with telemetry.span("sched:seal", slot=slot, page=pid, uid=sl.req.uid,
                            calls=len(self._attn_pos)):
            for pos in self._attn_pos:
                k_src, v_src = srcs[pos]
                pool = self.pools[pos]
                self.pools[pos] = self._seal(pool, k_src, v_src, at)
                if telemetry.enabled():
                    in_place &= all(leaf.is_deleted()
                                    for leaf in jax.tree.leaves(pool))
        telemetry.inc("sched/pages_sealed")
        if in_place:
            telemetry.inc("sched/seals_in_place")
        self._table[slot, page_idx] = pid
        sl.n_sealed = page_idx + 1

    def _seal_tails(self, slot: int) -> None:
        """Seal the (now full) tail page of ``slot``."""
        sl = self.slots[slot]
        page_idx = sl.len // self.page_size - 1
        srcs = {pos: (self.hot[pos]["k_tail"], self.hot[pos]["v_tail"])
                for pos in self._attn_pos}
        self._seal_into(slot, page_idx, srcs, row=slot, start=0)

    # ------------------------------------------------------------ prefill --
    def _finish_prefill(self, slot: int, tok: int) -> None:
        """Record the prefill-produced first token; EOS / budget may retire
        the request before it ever decodes."""
        sl = self.slots[slot]
        req = sl.req
        req.output.append(int(tok))
        sl.state = "decode"
        telemetry.request_event(req.uid, "first_token", slot=slot)
        if ((req.eos_id is not None and int(tok) == req.eos_id)
                or len(req.output) >= req.max_new_tokens):
            self._retire(slot)
            return
        telemetry.request_event(req.uid, "decode", slot=slot)
        self._tokens[slot] = req._feed(0, int(tok))

    def _serial_prefill(self, slot: int) -> None:
        """Monolithic one-shot prefill (compiles per prompt length) +
        head-of-line stall on the decode lane."""
        sl = self.slots[slot]
        plen = int(sl.req.prompt.shape[0])
        ps = self.page_size
        telemetry.request_event(sl.req.uid, "prefill", mode="serial",
                                prompt_len=plen)
        with telemetry.span("sched:prefill_serial", slot=slot,
                            prompt_len=plen):
            lg, caches = self._prefill(self.params,
                                       {"tokens": sl.req.prompt[None, :]})
        n_full = plen // ps
        srcs = {pos: (caches[pos]["k"], caches[pos]["v"])
                for pos in self._attn_pos}
        for j in range(n_full):
            self._seal_into(slot, j, srcs, row=0, start=j * ps)
        r = plen - n_full * ps
        for pos in self.hot:
            hp = self.hot[pos]
            if "k_tail" in hp:
                if r:
                    ck = caches[pos]["k"][:, 0, n_full * ps:plen]
                    cv = caches[pos]["v"][:, 0, n_full * ps:plen]
                    hp["k_tail"] = hp["k_tail"].at[:, slot, :r].set(
                        ck.astype(hp["k_tail"].dtype))
                    hp["v_tail"] = hp["v_tail"].at[:, slot, :r].set(
                        cv.astype(hp["v_tail"].dtype))
            else:
                hp["conv"] = hp["conv"].at[:, slot].set(
                    caches[pos]["conv"][:, 0].astype(hp["conv"].dtype))
                hp["state"] = hp["state"].at[:, slot].set(
                    caches[pos]["state"][:, 0])
        sl.len = plen
        # the monolithic executable owns the device for the whole prompt —
        # charge the decode lane one stall tick per chunk-equivalent.  (The
        # chunked lane pays the same per-chunk ticks but folds each into a
        # tick the decode batch also runs in; that asymmetry IS the
        # head-of-line blocking serving_bench measures.)
        self._stall += -(-plen // self.prefill_chunk)
        with telemetry.span("sched:sync"):
            tok = int(jnp.argmax(lg[0, -1, :self.cfg.vocab_size]))
        self._finish_prefill(slot, tok)

    def _prefill_slots(self) -> list:
        return [s for s in range(self.n_slots)
                if self.slots[s] is not None
                and self.slots[s].state == "prefill"]

    def _advance_prefill(self, slot: int) -> None:
        """Run one fixed-shape chunk of ``slot``'s prompt."""
        sl = self.slots[slot]
        prompt = np.asarray(sl.req.prompt)
        plen = int(prompt.shape[0])
        c = self.prefill_chunk
        start = sl.pf_start
        valid = min(c, plen - start)
        if start == 0:
            telemetry.request_event(sl.req.uid, "prefill", mode="chunked",
                                    prompt_len=plen)
        with telemetry.span("sched:prefill_chunk", slot=slot, start=start,
                            valid=valid, uid=sl.req.uid):
            with telemetry.span("sched:inputs"):
                toks = np.zeros((1, c), np.int32)
                toks[0, :valid] = prompt[start:start + valid]
                toks_d, table_d = jnp.asarray(toks), jnp.asarray(self._table)
                slot_d, start_d = jnp.int32(slot), jnp.int32(start)
                valid_d = jnp.int32(valid)
            lg, self.hot, chunk_kv = self._chunk_prefill(
                self.params, toks_d, self.pools, self.hot, table_d, slot_d,
                start_d, valid_d)
        new_len = start + valid
        ps = self.page_size
        srcs = {pos: (chunk_kv[pos]["k"], chunk_kv[pos]["v"])
                for pos in self._attn_pos}
        for j in range(sl.n_sealed, new_len // ps):
            self._seal_into(slot, j, srcs, row=0, start=j * ps - start)
        sl.pf_start = start + valid
        sl.len = new_len
        if sl.pf_start >= plen:
            with telemetry.span("sched:sync"):
                tok = int(jnp.argmax(lg[0, valid - 1, :self.cfg.vocab_size]))
            self._finish_prefill(slot, tok)

    # ------------------------------------------------------------- decode --
    def _retire(self, slot: int) -> None:
        sl = self.slots[slot]
        sl.req.done = True
        self._finished.append(sl.req)
        if telemetry.enabled():
            telemetry.inc("sched/retired")
            telemetry.request_event(sl.req.uid, "retired", slot=slot,
                                    n_tokens=len(sl.req.output))
        self.allocator.free(sl.pages)      # defrags the free list
        self._table[slot] = -1
        self.slots[slot] = None

    def _decode_slots(self) -> list:
        return [s for s in range(self.n_slots)
                if self.slots[s] is not None
                and self.slots[s].state == "decode"]

    def _run_decode(self, active: list) -> None:
        cache_len = np.zeros((self.n_slots,), np.int32)
        for s in range(self.n_slots):
            if self.slots[s] is not None:
                cache_len[s] = self.slots[s].len
        mask = np.zeros((self.n_slots,), bool)
        mask[active] = True
        args = {"n_active": len(active)}
        if telemetry.enabled():
            # sealed pages the attention reads against the pages it gathers
            args.update(pages_gathered=pages_gathered(self._table.shape),
                        pages_valid=sum(self.slots[s].n_sealed
                                        for s in active))
        with telemetry.span("sched:decode", **args):
            with telemetry.span("sched:inputs"):
                tok_d = jnp.asarray(self._tokens, jnp.int32)[:, None]
                len_d = jnp.asarray(cache_len)
                table_d = jnp.asarray(self._table)
                mask_d = jnp.asarray(mask)
            lg, self.hot = self._decode(self.params, tok_d, self.pools,
                                        self.hot, len_d, table_d, mask_d)
            # np.asarray blocks on the device step, so the token events
            # below carry post-compute wall-clock timestamps
            with telemetry.span("sched:sync"):
                nxt = np.asarray(
                    jnp.argmax(lg[:, -1, :self.cfg.vocab_size], axis=-1))
        with telemetry.span("sched:emit"):
            for s in active:
                sl = self.slots[s]
                req = sl.req
                tok = int(nxt[s])
                req.output.append(tok)
                telemetry.request_event(req.uid, "token", slot=s)
                sl.len += 1
                if sl.len % self.page_size == 0 \
                        and sl.len // self.page_size <= len(sl.pages):
                    self._seal_tails(s)
                if ((req.eos_id is not None and tok == req.eos_id)
                        or len(req.output) >= req.max_new_tokens
                        or sl.len >= self.max_len - 2):
                    self._retire(s)
                    continue
                self._tokens[s] = req._feed(len(req.output) - 1, tok)

    # -------------------------------------------------------- speculative --
    @staticmethod
    def _make_commit(ps: int):
        """One jitted writer: copy the first ``n_acc`` verify KV rows of
        ``slot``'s window into its hot tail at offset ``r`` — the rollback
        that makes rejected draft KV unobservable (it is simply never
        written)."""
        def commit(hot, chunk_kv, slot, r, n_acc):
            t = jnp.arange(ps)
            sel = (t >= r) & (t < r + n_acc)
            sel_b = sel[None, :, None, None]
            new_hot = {}
            for pos, hp in hot.items():
                if "k_tail" not in hp:
                    new_hot[pos] = hp
                    continue
                ck = chunk_kv[pos]["k"][:, 0]        # (g, C, KV, hd)
                cv = chunk_kv[pos]["v"][:, 0]
                src = jnp.clip(t - r, 0, ck.shape[1] - 1)
                kt = jnp.where(sel_b, jnp.take(ck, src, axis=1),
                               hp["k_tail"][:, slot])
                vt = jnp.where(sel_b, jnp.take(cv, src, axis=1),
                               hp["v_tail"][:, slot])
                new_hot[pos] = {"k_tail": hp["k_tail"].at[:, slot].set(kt),
                                "v_tail": hp["v_tail"].at[:, slot].set(vt)}
            return new_hot
        return commit

    def _run_speculative(self, active: list) -> None:
        """One draft/verify round over the decoding slots.

        Per slot: up to ``k_eff`` draft tokens (reduced-fidelity plan,
        batched through the draft decode lane), then a fixed-shape
        ``(1, k+1)`` verify step at full fidelity whose greedy predictions
        both judge the drafts (longest accepted prefix) and supply the
        bonus token — so every emitted token equals what plain greedy
        decode would have emitted.  ``k_eff`` caps at the hot tail's
        remaining room (``page_size - 1 - len % page_size``) so one round
        commits into one page, plus the request's token budget and the
        serving window.
        """
        ps = self.page_size
        C = self.speculative + 1
        base = {s: self.slots[s].len for s in active}
        k_eff = {}
        for s in active:
            sl = self.slots[s]
            k_eff[s] = max(0, min(
                self.speculative,
                ps - 1 - sl.len % ps,
                sl.req.max_new_tokens - len(sl.req.output) - 1,
                (self.max_len - 2) - sl.len - 1))
        max_k = max(k_eff.values(), default=0)
        drafts: dict = {s: [] for s in active}
        cache_len = np.zeros((self.n_slots,), np.int32)
        for s in range(self.n_slots):
            if self.slots[s] is not None:
                cache_len[s] = self.slots[s].len
        if max_k:
            # draft lane: the tail rows it writes are provisional — the
            # snapshot restore below rolls them back before verify
            hot0 = self.hot
            cur = np.array(self._tokens, np.int64)
            with telemetry.span("spec:draft", n_active=len(active), k=max_k):
                for j in range(max_k):
                    mask = np.zeros((self.n_slots,), bool)
                    cl = cache_len.copy()
                    for s in active:
                        mask[s] = j < k_eff[s]
                        cl[s] = base[s] + j
                    lg, self.hot = self._draft_decode(
                        self._draft_params,
                        jnp.asarray(cur, jnp.int32)[:, None], self.pools,
                        self.hot, jnp.asarray(cl), jnp.asarray(self._table),
                        jnp.asarray(mask))
                    with telemetry.span("sched:sync"):
                        nxt = np.asarray(jnp.argmax(
                            lg[:, -1, :self.cfg.vocab_size], axis=-1))
                    for s in active:
                        if j < k_eff[s]:
                            drafts[s].append(int(nxt[s]))
                            cur[s] = int(nxt[s])
            self.hot = hot0
            telemetry.inc("spec/drafted", sum(k_eff.values()))
        for s in active:
            sl = self.slots[s]
            req = sl.req
            start = base[s]
            toks = np.zeros((1, C), np.int32)
            toks[0, 0] = self._tokens[s]
            toks[0, 1:1 + len(drafts[s])] = drafts[s]
            with telemetry.span("spec:verify", slot=s, k=k_eff[s]):
                lg, chunk_kv = self._verify(
                    self.params, jnp.asarray(toks), self.pools, self.hot,
                    jnp.asarray(self._table), jnp.int32(s), jnp.int32(start))
                with telemetry.span("sched:sync"):
                    g = np.asarray(
                        jnp.argmax(lg[0, :, :self.cfg.vocab_size], axis=-1))
            n_acc = 0
            retired = False
            for j in range(k_eff[s] + 1):
                tok = int(g[j])
                req.output.append(tok)
                telemetry.request_event(req.uid, "token", slot=s)
                n_acc = j + 1
                if ((req.eos_id is not None and tok == req.eos_id)
                        or len(req.output) >= req.max_new_tokens
                        or start + n_acc >= self.max_len - 2):
                    retired = True
                    break
                fed = req._feed(len(req.output) - 1, tok)
                # a draft survives iff it matches what plain decode would
                # FEED next (== the greedy token, unless teacher-forced)
                if j < k_eff[s] and drafts[s][j] == fed:
                    continue
                self._tokens[s] = fed
                break
            self.hot = self._commit(self.hot, chunk_kv, jnp.int32(s),
                                    jnp.int32(start % ps), jnp.int32(n_acc))
            sl.len = start + n_acc
            telemetry.inc("spec/rounds")
            telemetry.inc("spec/accepted", n_acc - 1)
            if sl.len % ps == 0 and sl.len // ps <= len(sl.pages):
                self._seal_tails(s)
            if retired:
                self._retire(s)

    # -------------------------------------------------------------- drive --
    def step(self) -> int:
        """One scheduler tick: admit, advance one prefill chunk, decode all
        decoding slots.  Returns the number of requests that progressed."""
        with telemetry.span("sched:step", tick=self._steps):
            with telemetry.span("sched:admit"):
                self._admit()
            progressed = 0
            if self.prefill_mode == "chunked":
                pf = self._prefill_slots()
                if pf:
                    # round-robin by progress: least-advanced first
                    slot = min(pf, key=lambda s: (self.slots[s].pf_start, s))
                    self._advance_prefill(slot)
                    progressed += 1
            if telemetry.enabled():
                telemetry.inc("sched/ticks")
                telemetry.gauge("sched/queue_depth", len(self.queue))
            if self._stall > 0:
                # serial mode: the monolithic prefill still occupies the
                # device
                self._stall -= 1
                self._steps += 1
                telemetry.inc("sched/stall_ticks")
                return progressed + len(self._decode_slots())
            active = self._decode_slots()
            if active:
                if self.speculative:
                    self._run_speculative(active)
                else:
                    self._run_decode(active)
                progressed += len(active)
            self._steps += 1
            return progressed

    def run_to_completion(self, max_steps: int = 10_000) -> list:
        while (self.queue or any(s is not None for s in self.slots)) \
                and max_steps:
            self.step()
            max_steps -= 1
        out, self._finished = self._finished, []
        return out

    # -------------------------------------------------------------- stats --
    def cache_stats(self) -> dict:
        """Resident cache bytes vs the codec's Eq.-1/2 expectation (see
        :func:`repro.serving.pages.cache_stats`), plus allocator state."""
        out = pages_mod.cache_stats(self.pools, self.hot, self.spec,
                                    self.cfg, self.n_slots, self.max_len)
        out["allocator"] = self.allocator.defrag()
        out["attn_variant"] = self.spec.attn_variant
        out["steps"] = self._steps
        if self.speculative:
            from repro.engine import draft_plan_bytes
            out["speculative"] = dict(
                k=self.speculative, mode=self.draft_policy.mode,
                **draft_plan_bytes(self.draft_plan))
        return out
