"""Serving driver: batched prefill + decode with optional StruM-compressed
weights — the paper's deployment scenario (post-training quantization, no
retraining, vendor-side encode).

CPU-scale usage (examples/serve_strum.py wraps this):

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke \
        --strum mip2q --p 0.5 --L 5 --prompt-len 32 --gen 16 --batch 4

``--strum none`` serves dense weights (the INT8→bf16 baseline); any other
method (or ``--schedule sched.json``) builds a :class:`repro.engine`
``ExecutionPlan`` — packed payloads + registry-selected kernel variant per
leaf — and serves its params through the StruM-aware linear, printing the
weight-bytes ratio achieved (paper Eq. 1/2) and the per-variant plan
summary.  ``--backend interpret`` forces interpret-mode Pallas variants
per call (no env var needed).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.configs.base import get_config, get_smoke_config
from repro.core.policy import StruMConfig
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import model_defs
from repro.models.params import init_params
from repro.models.quantize import serve_tree_bytes


def pad_caches(caches, extra: int):
    """Grow attention caches by ``extra`` decode slots."""
    def f(path, x):
        name = str(getattr(path[-1], "key", ""))
        if name in ("k", "v"):
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, extra)
            return jnp.pad(x, pad)
        return x
    return jax.tree_util.tree_map_with_path(f, caches)


def serve(cfg, params, prompt: jnp.ndarray, gen: int, strum_kw: dict,
          mesh=None, rules=None):
    import contextlib
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        return _serve(cfg, params, prompt, gen, mesh, rules)


def _serve(cfg, params, prompt: jnp.ndarray, gen: int, mesh, rules):
    prefill_fn = jax.jit(make_prefill_step(cfg, mesh, rules))
    decode_fn = jax.jit(make_decode_step(cfg, mesh, rules))

    t0 = time.time()
    lg, caches = prefill_fn(params, {"tokens": prompt})
    caches = pad_caches(caches, gen + 1)
    toks = [jnp.argmax(lg[:, -1, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]]
    t_prefill = time.time() - t0

    t0 = time.time()
    n = prompt.shape[1]
    for i in range(gen):
        lg, caches = decode_fn(params, toks[-1], caches, jnp.int32(n + i))
        toks.append(jnp.argmax(lg[:, -1, :cfg.vocab_size], -1)
                    .astype(jnp.int32)[:, None])
    jax.block_until_ready(toks[-1])
    t_decode = time.time() - t0
    return jnp.concatenate(toks, axis=1), t_prefill, t_decode


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--strum", default="mip2q",
                    choices=["none", "sparsity", "dliq", "mip2q"])
    ap.add_argument("--p", type=float, default=0.5)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--L", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--schedule", default=None,
                    help="autotuned StruMSchedule JSON (overrides --strum)")
    ap.add_argument("--backend", default=None,
                    choices=["auto", "pallas", "interpret", "xla"],
                    help="pin the engine's kernel-variant selection")
    ap.add_argument("--mesh", default=None, metavar="FSDPxTP",
                    help="serve on a host mesh, e.g. 4x2 (needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count="
                         "N); plans then select sharded:* variants")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged continuous-batching "
                         "runtime (BatchScheduler) instead of the "
                         "single-stream dense-cache loop")
    ap.add_argument("--kv-cache", default="none",
                    choices=["none", "sparsity", "dliq", "mip2q"],
                    help="(--paged) pack sealed KV pages with this codec")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill", default="chunked",
                    choices=["chunked", "serial"])
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="(--paged) self-speculative decoding: draft up to "
                         "K tokens per slot per tick from the packed "
                         "payload read at reduced fidelity, then verify at "
                         "full fidelity (token-identical greedy output)")
    ap.add_argument("--draft", default="histream",
                    choices=["histream", "maskfree_p"],
                    help="(--speculative) which streams the draft lane "
                         "reads: histream = mask+hi (skip lo), "
                         "maskfree_p = hi only (skip mask+lo)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record telemetry and write a Chrome-trace JSON "
                         "to PATH at exit (same as STRUM_TRACE=PATH); "
                         "open in Perfetto or chrome://tracing")
    args = ap.parse_args(argv)

    if args.trace:
        telemetry.configure(trace_path=args.trace)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(model_defs(cfg), seed=args.seed,
                         dtype_override="float32")
    dense_bytes = serve_tree_bytes(params)

    mesh = rules = None
    if args.mesh is not None:
        from repro.launch.mesh import make_host_mesh
        from repro.models.sharding import rules_for_mesh
        data, model = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(data=data, model=model)
        rules = rules_for_mesh(mesh)

    plan = None
    if args.schedule is not None or args.strum != "none":
        from repro.launch.steps import build_serving_plan
        if args.schedule is not None:
            from repro.autotune.schedule import StruMSchedule
            sched = StruMSchedule.load(args.schedule)
            plan = build_serving_plan(params, schedule=sched,
                                      backend=args.backend, mesh=mesh,
                                      rules=rules)
            note = f"schedule {args.schedule}"
        else:
            scfg = StruMConfig(method=args.strum, p=args.p, q=args.q,
                               L=args.L)
            cfg = dataclasses.replace(cfg, strum=scfg)
            plan = build_serving_plan(params, cfg=scfg,
                                      backend=args.backend, mesh=mesh,
                                      rules=rules)
            note = f"theoretical vs int8 r={scfg.compression_ratio:.4f}"
        comp_bytes = plan.serve_bytes()
        summ = plan.summary()
        print(f"weights: dense {dense_bytes/1e6:.2f} MB -> StruM "
              f"{comp_bytes/1e6:.2f} MB (x{comp_bytes/dense_bytes:.3f}; "
              f"{note})")
        print(f"plan: {summ['n_entries']} entries, variants "
              f"{summ['variant_distribution']} (backend {summ['backend']})")
        params = plan.params
    else:
        print(f"weights: dense {dense_bytes/1e6:.2f} MB")

    key = jax.random.PRNGKey(args.seed)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    if args.paged:
        from repro.core.policy import StruMConfig as _SC
        from repro.serving import BatchScheduler, Request
        kv = None if args.kv_cache == "none" else \
            _SC(method=args.kv_cache, p=0.5, q=4, L=7)
        max_len = args.prompt_len + args.gen + args.page_size
        sched = BatchScheduler(cfg, params, n_slots=args.batch,
                               max_len=max_len, mesh=mesh, rules=rules,
                               plan=plan, kv_cache=kv,
                               page_size=args.page_size,
                               prefill=args.prefill,
                               speculative=args.speculative, draft=args.draft)
        for i in range(args.batch):
            sched.submit(Request(uid=i, prompt=prompt[i],
                                 max_new_tokens=args.gen + 1))
        t0 = time.time()
        done = sched.run_to_completion()
        dt = time.time() - t0
        st = sched.cache_stats()
        print(f"paged serve: {len(done)} requests in {dt*1e3:.1f} ms "
              f"({st['steps']} ticks, {args.prefill} prefill); cache "
              f"{st['codec']} x{st['ratio_vs_int8']:.3f} vs int8 pages")
        if args.speculative:
            rec = telemetry.current()
            if rec is not None and rec.counter("spec/drafted"):
                acc = rec.counter("spec/accepted") / rec.counter("spec/drafted")
                print(f"speculative: k={args.speculative} draft={args.draft} "
                      f"acceptance {acc:.3f} "
                      f"(payload ratio {st['speculative']['ratio']:.3f})")
        print("sample:", done[0].output[:16])
        _print_telemetry()
        return 0
    toks, t_p, t_d = serve(cfg, params, prompt, args.gen, {}, mesh=mesh,
                           rules=rules)
    print(f"prefill {t_p*1e3:.1f} ms; decode {t_d*1e3:.1f} ms "
          f"({args.gen} steps, {t_d/args.gen*1e3:.2f} ms/tok)")
    print("sample:", toks[0, :16].tolist())
    _print_telemetry()
    return 0


def _print_telemetry():
    """End-of-run summary of the active recorder (--trace / STRUM_TRACE)."""
    rec = telemetry.current()
    if rec is None:
        return
    lat = rec.latency_summary()
    if lat["n_requests"]:
        def ms(v):
            return "n/a" if v is None else f"{v/1e3:.1f} ms"
        gp = lat["goodput_tok_s"]
        print(f"telemetry: {lat['n_retired']}/{lat['n_requests']} retired; "
              f"ttft p50/p99 {ms(lat['ttft_p50_us'])}/"
              f"{ms(lat['ttft_p99_us'])}; tok p50/p99 "
              f"{ms(lat['tok_p50_us'])}/{ms(lat['tok_p99_us'])}; goodput "
              f"{'n/a' if gp is None else f'{gp:.1f} tok/s'}")
    disp = rec.counters("dispatch/variant/")   # keys come back prefix-free
    if disp:
        counts = {k: int(v) for k, v in sorted(disp.items())}
        print(f"telemetry: dispatch {counts}; packed bytes "
              f"{int(rec.counter('dispatch/packed_bytes'))}")
    cache = rec.counters("cache/")
    if cache:
        print(f"telemetry: cache {dict(sorted(cache.items()))}")


if __name__ == "__main__":
    sys.exit(main())
