#!/usr/bin/env python3
"""Serve a real-width model on TPU through compiled Pallas kernels, and check it.

    python chip_smoke.py             # one chip: OLMo-1B, paged packed serving
    python chip_smoke.py --chips 4   # four chips: Qwen2-7B on a 2x2 FSDP x TP mesh

One chip (the default).  OLMo-1B at its published widths (16 layers, d_model
2048, 16 heads, d_ff 8192, vocab 50304) with seeded random bf16 weights:

1. ``engine.build_plan`` packs every linear as MIP2Q p=0.5 (the mixed
   payload, which lowers to ``pallas:onehot``);
2. ``BatchScheduler`` serves 4 requests (prompts of 128-512 tokens, 32 new
   tokens each) over a paged KV cache packed as DLIQ p=0.5 q=4, with chunked
   prefill, so attention runs the fused ``cache:attn_fused`` kernel;
3. the same weights and prompts are served again through an independent
   path: a ``backend="xla"`` plan (``xla:dequant``) with
   ``cache_backend="xla"`` (``cache:attn_unfused``), teacher-forced on the
   first run's tokens.  Per-position agreement must reach ``AGREE_MIN`` and
   next-token logits of both plans' monolithic prefill of the first prompt
   must agree within ``LOGIT_RTOL`` (relative L2 over the true vocabulary).

Four chips (``--chips 4``) runs only the distributed phase: Qwen2-7B at
published widths (28 layers, d_model 3584, d_ff 18944, vocab 152064) on a
2x2 (data, model) mesh.  The parameters are drawn already sharded; the packed
plan serves through ``sharded:gather_pallas`` and is compared with a
``backend="xla"`` plan (``sharded:gather_dequant``) on the same mesh, by
prefill logits and teacher-forced greedy decode.

Everything runs in this one process, which holds the chip(s).  The script
refuses to run anywhere but a TPU, refuses interpret-mode Pallas, and fails
if any leaf or the attention would take a non-Pallas path.  The last line of
standard output is ``{"ok": true, "device": {...}}`` only when every phase
passed; any failure exits non-zero without it.

JAX's persistent compile cache: when ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and this script sets nothing; otherwise the cache lives at
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
PROMPT_LENS = (128, 256, 384, 512)
NEW_TOKENS = 32
PAGE_SIZE = 16
PREFILL_CHUNK = 64     # prompt tokens per prefill tick (4 pages)
MAX_LEN = 576          # 36 pages: the longest prompt + 32 new tokens fit
# teacher-forced token agreement and prefill-logit error between the Pallas
# plan and the xla plan.  Both read the same packed weights and KV pages;
# they differ in float order and in where the dequantized weight is rounded
# (f32 in the kernel, bf16 in the xla dot): ~2**-9 relative per matmul,
# a few 1e-2 after 16 bf16 layers, and it flips only near-tied greedy
# picks.  A decode fault is far louder: one wrong position in each 16-wide
# block already moves every matmul output by ~25%.
AGREE_MIN = 0.9
LOGIT_RTOL = 0.1


class SmokeFailure(Exception):
    """A phase produced a wrong or unexpected result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def compile_cache_dir(environ, root: str = ROOT):
    """Where this process should put JAX's compile cache: ``None`` when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads the variable itself),
    else the fixed ``<checkout>/.jax_cache``."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(root, ".jax_cache")


class CompileClock:
    """Sums XLA backend-compile seconds reported by ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


_T0 = time.time()


def log(msg: str) -> None:
    print(f"[{time.time() - _T0:7.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------- one chip --

def _prompts(vocab: int, lens, seed: int = SEED):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, vocab, size=(n,)), jnp.int32)
            for n in lens]


def _pallas_only(dist: dict) -> None:
    bad = {v: n for v, n in dist.items() if not v.startswith("pallas:")}
    check(not bad, f"plan left the Pallas family: {bad}")


def _rel_err(got, want) -> float:
    import jax.numpy as jnp
    d = (got.astype(jnp.float32) - want.astype(jnp.float32))
    return float(jnp.linalg.norm(d) / jnp.linalg.norm(want.astype(jnp.float32)))


def _serve(cfg, plan, prompts, *, force=None, **sched_kw):
    from repro.serving import BatchScheduler, Request
    sched = BatchScheduler(cfg, None, plan=plan, n_slots=len(prompts),
                           max_len=MAX_LEN, page_size=PAGE_SIZE,
                           prefill="chunked", prefill_chunk=PREFILL_CHUNK,
                           **sched_kw)
    for i, p in enumerate(prompts):
        sched.submit(Request(uid=i, prompt=p, max_new_tokens=NEW_TOKENS,
                             force_tokens=None if force is None else force[i]))
    done = {r.uid: r.output for r in sched.run_to_completion()}
    check(sorted(done) == list(range(len(prompts))),
          f"requests finished: {sorted(done)}")
    check(all(len(o) == NEW_TOKENS for o in done.values()),
          f"tokens per request: {[len(o) for o in done.values()]}")
    return done, sched.cache_stats()


def one_chip(clock: CompileClock, cfg=None, lens=PROMPT_LENS) -> dict:
    """OLMo-1B (or ``cfg``) through the Pallas plan, checked against the xla
    plan; returns the agreement numbers it printed."""
    import jax
    import numpy as np
    from repro import engine
    from repro.configs import get_config
    from repro.core.policy import StruMConfig
    from repro.launch.steps import build_serving_plan, make_prefill_step
    from repro.models import model_defs
    from repro.models.params import init_params

    cfg = cfg or get_config("olmo_1b")
    wcfg = StruMConfig(method="mip2q", p=0.5)
    kv = StruMConfig(method="dliq", p=0.5, q=4)
    params = init_params(model_defs(cfg), seed=SEED,
                         dtype_override="bfloat16")
    prompts = _prompts(cfg.vocab_size, lens)

    # -- the Pallas path: build_plan -> BatchScheduler -> fused attention --
    plan = engine.build_plan(params, cfg=wcfg)
    dist = plan.summary()["variant_distribution"]
    log(f"plan[pallas]: {dist}")
    _pallas_only(dist)
    out, stats = _serve(cfg, plan, prompts, kv_cache=kv)
    log(f"serve[pallas]: attn_variant={stats['attn_variant']} "
        f"codec={stats['codec']} ticks={stats['steps']} "
        f"compile_s={clock.lap():.1f}")
    check(stats["attn_variant"] == "cache:attn_fused",
          f"attention took {stats['attn_variant']}")
    check(stats["codec"] == "cache:pallas_decode",
          f"cache codec took {stats['codec']}")

    # -- the independent path: xla dequant weights, unfused attention -----
    plan_x = build_serving_plan(params, cfg=wcfg, backend="xla")
    dist_x = plan_x.summary()["variant_distribution"]
    log(f"plan[xla]: {dist_x}")
    check(set(dist_x) == {"xla:dequant"}, f"xla plan selected {dist_x}")
    out_x, stats_x = _serve(cfg, plan_x, prompts, kv_cache=kv,
                            cache_backend="xla",
                            force=[out[i] for i in range(len(prompts))])
    check(stats_x["attn_variant"] == "cache:attn_unfused",
          f"xla attention took {stats_x['attn_variant']}")
    per_req = [float(np.mean(np.array(out[i]) == np.array(out_x[i])))
               for i in range(len(prompts))]
    agree = float(np.mean(per_req))
    log(f"serve[xla]: attn_variant={stats_x['attn_variant']} "
        f"codec={stats_x['codec']} compile_s={clock.lap():.1f}")
    log(f"agreement: teacher-forced {agree:.4f} per request "
        f"{[round(a, 4) for a in per_req]} (min {AGREE_MIN})")
    check(agree >= AGREE_MIN, f"teacher-forced agreement {agree:.4f} "
                              f"< {AGREE_MIN}")

    # -- next-token logits of both plans' prefill of the first prompt ------
    prefill = jax.jit(make_prefill_step(cfg))
    batch = {"tokens": prompts[0][None, :]}
    lg, _ = prefill(plan.params, batch)
    lg_x, _ = prefill(plan_x.params, batch)
    lg, lg_x = lg[..., :cfg.vocab_size], lg_x[..., :cfg.vocab_size]
    check(lg.shape == (1, 1, cfg.vocab_size), f"logits {lg.shape}")
    check(bool(jax.numpy.isfinite(lg).all()), "non-finite Pallas logits")
    rel = _rel_err(lg, lg_x)
    top1 = float((lg.argmax(-1) == lg_x.argmax(-1)).mean())
    log(f"prefill logits: rel_l2={rel:.3e} (max {LOGIT_RTOL}) "
        f"max_abs={float(abs(lg - lg_x).max()):.3e} top1_agree={top1:.4f} "
        f"compile_s={clock.lap():.1f}")
    check(rel <= LOGIT_RTOL, f"prefill logits rel err {rel:.3e} > "
                             f"{LOGIT_RTOL}")
    return {"agreement": agree, "prefill_rel_l2": rel}


# -------------------------------------------------------------- four chips --

def _place(tree, shardings):
    """``device_put`` each array of ``tree`` that ``shardings`` gives a
    layout; static plan metadata (``cfg``, ``spec``) passes through."""
    import jax
    if isinstance(tree, dict):
        return {k: _place(v, shardings[k]) if k in shardings else v
                for k, v in tree.items()}
    return jax.device_put(tree, shardings)


def four_chips(clock: CompileClock, batch: int = 4, prompt_len: int = 128,
               gen: int = 8) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.policy import StruMConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import pad_caches
    from repro.launch.steps import (build_serving_plan, make_decode_step,
                                    make_prefill_step)
    from repro.models import model_defs
    from repro.models.params import init_params, param_shardings
    from repro.models.quantize import packed_model_defs
    from repro.models.sharding import rules_for_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, JAX found "
                                   f"{len(jax.devices())}")
    wcfg = StruMConfig(method="mip2q", p=0.5)
    cfg = dataclasses.replace(get_config("qwen2_7b"), strum=wcfg)
    mesh = make_host_mesh(data=2, model=2)
    rules = rules_for_mesh(mesh)
    defs = model_defs(cfg)
    params = init_params(defs, seed=SEED, dtype_override="bfloat16",
                         shardings=param_shardings(defs, mesh, rules))
    packed_shardings = param_shardings(packed_model_defs(cfg), mesh, rules)

    def plan_for(backend):
        plan = build_serving_plan(params, cfg=wcfg, backend=backend,
                                  mesh=mesh, rules=rules)
        # packed payloads go to the layout the FSDP x TP rules give them
        return (plan.summary()["variant_distribution"],
                _place(plan.params, packed_shardings))

    prompt = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(batch, prompt_len)), jnp.int32)
    prefill = jax.jit(make_prefill_step(cfg, mesh, rules))
    decode = jax.jit(make_decode_step(cfg, mesh, rules))

    def run(served, force=None):
        with mesh:
            lg, caches = prefill(served, {"tokens": prompt})
            caches = pad_caches(caches, gen + 1)
            pred = [jnp.argmax(lg[:, -1, :cfg.vocab_size], -1)]
            for i in range(gen):
                tok = pred[-1] if force is None else force[:, i]
                step_lg, caches = decode(served, tok[:, None].astype(jnp.int32),
                                         caches, jnp.int32(prompt_len + i))
                pred.append(jnp.argmax(step_lg[:, -1, :cfg.vocab_size], -1))
        return lg[..., :cfg.vocab_size], jnp.stack(pred, axis=1)

    dist, served = plan_for(None)
    log(f"plan[pallas, 2x2 mesh]: {dist}")
    check(set(dist) == {"sharded:gather_pallas"},
          f"mesh plan selected {dist}")
    lg, toks = run(served)
    check(bool(jnp.isfinite(lg).all()), "non-finite Pallas logits")
    log(f"serve[pallas, 2x2 mesh]: compile_s={clock.lap():.1f}")
    del served

    dist_x, served_x = plan_for("xla")
    log(f"plan[xla, 2x2 mesh]: {dist_x}")
    check(set(dist_x) == {"sharded:gather_dequant"},
          f"xla mesh plan selected {dist_x}")
    lg_x, toks_x = run(served_x, force=toks)
    rel = _rel_err(lg, lg_x)
    agree = float((toks == toks_x).mean())
    log(f"serve[xla, 2x2 mesh]: compile_s={clock.lap():.1f}")
    log(f"agreement: teacher-forced {agree:.4f} (min {AGREE_MIN}); prefill "
        f"logits rel_l2={rel:.3e} (max {LOGIT_RTOL})")
    check(agree >= AGREE_MIN, f"teacher-forced agreement {agree:.4f} "
                              f"< {AGREE_MIN}")
    check(rel <= LOGIT_RTOL, f"prefill logits rel err {rel:.3e} > "
                             f"{LOGIT_RTOL}")


# -------------------------------------------------------------------- main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh Qwen2-7B phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if os.environ.get("STRUM_INTERPRET"):
        print("chip_smoke: STRUM_INTERPRET is set; refusing interpret-mode "
              "Pallas", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.kernels.ops import default_interpret
    except ImportError as e:
        print(f"chip_smoke: run from the repository checkout ({e})",
              file=sys.stderr)
        return 2
    if default_interpret():
        print("chip_smoke: Pallas would run in interpret mode",
              file=sys.stderr)
        return 2
    cache = compile_cache_dir(os.environ)
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", cache)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device['kind']} x{device['count']} "
        f"(compile cache: {cache or os.environ['JAX_COMPILATION_CACHE_DIR']})")
    try:
        if args.chips == 4:
            four_chips(clock)
        else:
            one_chip(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log("all phases passed")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
