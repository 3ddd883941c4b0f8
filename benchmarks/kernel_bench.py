"""Per-variant StruM kernel microbenchmark + plan-selection smoke check.

For every registered kernel variant that supports a config, measures the
call (tokens/s at the benchmark shape) and the *measured operand byte
footprint* vs a dense int8 / bf16 matmul, plus the projected v5e HBM-bound
decode latency (bytes / 819 GB/s) — the quantity the paper's compression
ratio converts into.  Wall-clock in interpret mode is not meaningful for a
TPU kernel; it is reported for relative comparison between decode paths
only.

``check_selection()`` asserts that plan construction picks the expected
registry variant for each config — both 2-D leaves and expert stacks (the
``pallas:grouped*`` family) — and CI runs this in interpret mode
(``python -m benchmarks.kernel_bench --smoke``) so a registry/predicate
regression fails fast without a TPU.  The grouped section additionally
benchmarks expert-stack tokens/s through the two served dispatch paths
(compressed grouped kernel vs dequant + batched dot).

``--sharded`` forces an 8-host-device FSDP×TP mesh and benchmarks the
engine's ``sharded:*`` family: per-variant tokens/s plus the *measured*
all-gather bytes (packed payload vs the dense-gather equivalent — the
Eq. 1/2 wire ratio).  With ``--smoke`` it also asserts a packed FSDP leaf
selects ``sharded:gather_pallas`` under a pallas-family backend.

Output: ``name,us_per_call,derived`` CSV rows + results/kernel_bench.json.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine, telemetry
from repro.core.apply import pack_array
from repro.core.policy import StruMConfig

HBM_BW = 819e9

SHAPES = [  # (M, K, N) — decode-ish GEMVs and a prefill tile; K=1536 is the
    # w=12-divisible shape that exercises the any-w dense path
    (1, 4096, 4096), (8, 4096, 14336), (16, 2048, 8192), (128, 1024, 4096),
    (8, 1536, 4096),
]
SMOKE_SHAPES = [(1, 256, 512), (8, 128, 256), (4, 96, 256)]

# expert-stack shapes (E, C, K, N) for the grouped family — the per-expert
# capacity C plays the M role; K=1500 exercises K % w != 0 block padding.
# Sized so E·K·N stays near the largest 2-D shape: interpret-mode decode
# cost scales with total decoded weights and the full grid budgets one
# call per path.
GROUPED_SHAPES = [(4, 16, 2048, 8192), (4, 32, 1500, 4096)]
SMOKE_GROUPED_SHAPES = [(2, 4, 120, 256)]

# config grid: (label, cfg) — includes both specialization extremes
CONFIGS = [
    ("mip2q_p0.5", StruMConfig(method="mip2q", p=0.5, L=5)),
    ("dliq_p0.5", StruMConfig(method="dliq", p=0.5, q=4)),
    ("sparsity_p0.5", StruMConfig(method="sparsity", p=0.5)),
    ("dliq_p1.0", StruMConfig(method="dliq", p=1.0, q=4)),
    ("mip2q_p1.0", StruMConfig(method="mip2q", p=1.0, L=5)),
    ("dliq_p0.0", StruMConfig(method="dliq", p=0.0, q=4)),
    ("dliq_w12_p0.0", StruMConfig(method="dliq", p=0.0, q=4, w=12)),
]

# what the registry must select per config under a pallas-family backend
EXPECTED_PALLAS = {
    "mip2q_p0.5": "pallas:onehot",
    "dliq_p0.5": "pallas:onehot",
    "sparsity_p0.5": "pallas:onehot",
    "dliq_p1.0": "pallas:maskfree",
    "mip2q_p1.0": "pallas:maskfree",
    "dliq_p0.0": "pallas:dense",
    "dliq_w12_p0.0": "pallas:dense",   # no w%8 constraint on the hi-only path
}

# cache codecs through the fused-attention partition (attn=True contexts):
# packed codecs fuse page-gather + decode + flash-decode attention; p=1.0
# upgrades to the maskfree kernel; fp passthrough stays on the
# gather-then-einsum fallback
ATTN_CODECS = [
    ("fp", None),
    ("dliq_p0.5", StruMConfig(method="dliq", p=0.5, q=4)),
    ("mip2q_p0.5", StruMConfig(method="mip2q", p=0.5, L=7)),
    ("sparsity_p0.5", StruMConfig(method="sparsity", p=0.5)),
    ("dliq_p1.0", StruMConfig(method="dliq", p=1.0, q=4)),
]
EXPECTED_ATTN = {
    "fp": "cache:attn_unfused",
    "dliq_p0.5": "cache:attn_fused",
    "mip2q_p0.5": "cache:attn_fused",
    "sparsity_p0.5": "cache:attn_fused",
    "dliq_p1.0": "cache:attn_fused_maskfree",
}

# ... and for expert-stack leaves (info.lead != ()): the grouped family
EXPECTED_GROUPED = {
    "mip2q_p0.5": "pallas:grouped",
    "dliq_p0.5": "pallas:grouped",
    "sparsity_p0.5": "pallas:grouped",
    "dliq_p1.0": "pallas:grouped_maskfree",
    "mip2q_p1.0": "pallas:grouped_maskfree",
    "dliq_p0.0": "pallas:grouped_dense",
    "dliq_w12_p0.0": "pallas:grouped_dense",
}


def check_selection(verbose: bool = True) -> None:
    """Assert plan construction picks the expected variant per config."""
    info = engine.LeafInfo(k_dim=256, n_out=512)
    ginfo = engine.LeafInfo(k_dim=256, n_out=512, lead=(8,))
    for label, cfg in CONFIGS:
        got = engine.select_variant(cfg, info, backend="interpret").name
        want = EXPECTED_PALLAS[label]
        assert got == want, f"{label}: selected {got}, expected {want}"
        gg = engine.select_variant(cfg, ginfo, backend="interpret").name
        gw = EXPECTED_GROUPED[label]
        assert gg == gw, f"{label} (stacked): selected {gg}, expected {gw}"
        # auto off-TPU must stay on the portable dequant path
        if jax.default_backend() != "tpu":
            auto = engine.select_variant(cfg, info).name
            assert auto == "xla:dequant", (label, auto)
            gauto = engine.select_variant(cfg, ginfo).name
            assert gauto == "xla:dequant", (label, gauto)
    # and through an actual plan: heterogeneous tree -> per-leaf variants
    params = {"a": {"w": jnp.zeros((256, 512))}, "b": {"w": jnp.zeros((256, 512))}}
    from repro.autotune.schedule import StruMSchedule
    sched = StruMSchedule(assignments={
        "a/w": StruMConfig(method="mip2q", p=0.5, L=5),
        "b/w": StruMConfig(method="dliq", p=1.0, q=4)})
    plan = engine.build_plan(params, schedule=sched, backend="interpret",
                             pack=False)
    assert plan.variants() == {"a/w": "pallas:onehot",
                               "b/w": "pallas:maskfree"}, plan.variants()
    # expert-stack plan: stacked /moe/ leaves select the grouped family,
    # never the dequant fallback, under a pallas backend
    eparams = {"blocks": {"moe": {"wi": jnp.zeros((4, 256, 512)),
                                  "wo": jnp.zeros((4, 512, 256))}}}
    esched = StruMSchedule(assignments={
        "blocks/moe/wi": StruMConfig(method="mip2q", p=0.5, L=5),
        "blocks/moe/wo": StruMConfig(method="dliq", p=1.0, q=4)})
    eplan = engine.build_plan(eparams, schedule=esched, backend="interpret",
                              pack=False)
    assert eplan.variants() == {
        "blocks/moe/wi": "pallas:grouped",
        "blocks/moe/wo": "pallas:grouped_maskfree"}, eplan.variants()
    assert "xla:dequant" not in eplan.summary()["variant_distribution"]
    if verbose:
        print("selection check: "
              f"{len(CONFIGS)} configs (2-D + stacked) + heterogeneous and "
              f"expert-stack plans OK")


def run_attn_rows(smoke: bool = False) -> list:
    """Fused paged decode attention vs the gather-then-einsum path.

    One token per slot attends over ``pp`` sealed pages per codec; the
    fused kernel's sealed-pool HBM read is the mask+hi+lo payload, the
    unfused path additionally materializes the decoded fp pages before its
    einsum.  Also asserts the attn-partition selection map
    (``EXPECTED_ATTN``) — the serving-lane analogue of
    ``check_selection``.
    """
    from repro.engine import cache as ec
    rng = np.random.default_rng(0)
    if smoke:
        ps, kv, hd, n_pages, b, pp, rep = 16, 2, 16, 8, 2, 4, 2
    else:
        ps, kv, hd, n_pages, b, pp, rep = 64, 4, 64, 64, 4, 16, 4
    feat = kv * hd
    rows = []
    for label, cfg in ATTN_CODECS:
        fused = ec.build_cache_spec(cfg, page_size=ps, feat=feat,
                                    backend="interpret")
        unfused = ec.build_cache_spec(cfg, page_size=ps, feat=feat,
                                      backend="xla")
        assert fused.attn_variant == EXPECTED_ATTN[label], \
            (label, fused.attn_variant)
        assert unfused.attn_variant == "cache:attn_unfused", unfused

        def mkpool():
            pages = jnp.asarray(
                rng.normal(size=(n_pages, ps, feat)).astype(np.float32))
            if not fused.packed:
                return {"pages": pages}
            return jax.vmap(lambda pg: ec.encode_page(pg, cfg))(pages)
        pool = {"k": mkpool(), "v": mkpool()}
        qf = jnp.asarray(rng.normal(size=(b, kv, rep, hd)).astype(np.float32))
        table = jnp.asarray(rng.permutation(n_pages)[:b * pp]
                            .reshape(b, pp).astype(np.int32))
        n_valid = jnp.full((b,), pp, jnp.int32)

        fp_bytes = 2 * b * pp * ps * feat * 4      # decoded/raw pages, f32
        packed = fp_bytes if not fused.packed else \
            2 * b * pp * ec.page_payload_bytes(ps, feat, cfg)
        y_ref, tol = None, None
        for spec in (fused, unfused):
            name = spec.attn_variant
            is_fused = name != "cache:attn_unfused"
            reps = 1 if (is_fused and not smoke) else 3
            t_call, y = _bench_call(ec.attn_sealed_partial, pool, qf,
                                    table, n_valid, spec, reps=reps)
            if y_ref is None:
                y_ref = y
                tol = 1e-4 * max(1.0, float(jnp.max(jnp.abs(y[0]))))
            err = max(float(jnp.max(jnp.abs(a - r)))
                      for a, r in zip(y, y_ref))
            rows.append({
                "config": f"attn_{label}", "variant": name,
                "m": b * rep * kv, "k": pp * ps, "n": hd,
                "err_tol": tol,
                "packed_bytes": packed,
                "fp_intermediate_bytes": 0 if is_fused else fp_bytes,
                "ratio_vs_int8": packed / (fp_bytes // 4),
                "ratio_vs_bf16": packed / (fp_bytes // 2),
                "proj_decode_us_bf16": (fp_bytes // 2) / HBM_BW * 1e6,
                "proj_decode_us_strum": packed / HBM_BW * 1e6,
                "sec_per_call": t_call,
                "tokens_per_s": b / t_call,
                "max_abs_err": err,
            })
    return rows


def _bench_call(fn, *args, reps: int = 3, **kw) -> tuple[float, jnp.ndarray]:
    """reps=1 skips the warmup call too — interpret-mode Pallas at serving
    shapes costs minutes per call, so the full grid budgets one call per
    variant (matching the old single-shot benchmark)."""
    if reps > 1:
        jax.block_until_ready(fn(*args, **kw))
    t0 = time.time()
    for _ in range(reps):
        y = fn(*args, **kw)
    jax.block_until_ready(y)
    return (time.time() - t0) / reps, y


def run(smoke: bool = False):
    check_selection()
    rng = np.random.default_rng(0)
    shapes = SMOKE_SHAPES if smoke else SHAPES
    # smoke: one representative per pallas variant (onehot/maskfree/dense)
    smoke_labels = ("mip2q_p0.5", "dliq_p1.0", "dliq_p0.0")
    configs = [c for c in CONFIGS if c[0] in smoke_labels] if smoke \
        else CONFIGS
    if smoke:
        assert len(configs) == len(smoke_labels), configs
    rows = []
    for label, cfg in configs:
        covered = False
        for (m, k, n) in shapes:
            if k % cfg.w:
                continue
            covered = True
            wt = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
            x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
            packed = pack_array(wt, cfg)
            info = engine.LeafInfo(k_dim=k, n_out=n)
            w_bytes = packed.payload_bytes()
            dense_bf16, dense_int8 = k * n * 2, k * n
            from repro.kernels import ref
            y_ref = ref.strum_matmul_ref(x, packed)
            # f32 accumulation-order noise grows with |y|; tolerate relative
            # to the output scale (the tests' rtol-style check)
            tol = 1e-4 * max(1.0, float(jnp.max(jnp.abs(y_ref))))
            for name, var in sorted(engine.list_variants().items()):
                # sharded variants need mesh context (run_sharded covers
                # them) and cache:* codecs take page payloads, not (x, W) —
                # neither fits the 2-D matmul sweep's calling convention
                if (var.family == "reference" or var.sharded or var.cache
                        or not var.supports(cfg, info)):
                    continue
                interpret = True if var.family == "pallas" else None
                reps = 1 if (var.family == "pallas" and not smoke) else 3
                t_call, y = _bench_call(var.fn, x, packed,
                                        interpret=interpret, reps=reps)
                err = float(jnp.max(jnp.abs(y - y_ref)))
                rows.append({
                    "config": label, "variant": name, "m": m, "k": k, "n": n,
                    "err_tol": tol,
                    "packed_bytes": w_bytes,
                    "ratio_vs_int8": w_bytes / dense_int8,
                    "ratio_vs_bf16": w_bytes / dense_bf16,
                    "proj_decode_us_bf16": dense_bf16 / HBM_BW * 1e6,
                    "proj_decode_us_strum": w_bytes / HBM_BW * 1e6,
                    "sec_per_call": t_call,
                    "tokens_per_s": m / t_call,
                    "max_abs_err": err,
                })
        if not covered:
            print(f"# {label}: no benchmark shape has K % w == 0 "
                  f"(w={cfg.w}) — config skipped")

    # grouped expert-stack shapes: benchmark the two *served* dispatch paths
    # (compressed pallas:grouped* vs the dequant + batched-dot fallback).
    # No K % w skip — block padding is the grouped wrapper's job.
    from repro.engine.dispatch import dequant_leaf, dispatch_grouped
    from repro.models.quantize import _pack_leaf
    gshapes = SMOKE_GROUPED_SHAPES if smoke else GROUPED_SHAPES
    for label, cfg in configs:
        for (e, c, k, n) in gshapes:
            wt = jnp.asarray(rng.normal(size=(e, k, n)).astype(np.float32))
            x = jnp.asarray(rng.normal(size=(e, c, k)).astype(np.float32))
            leaf = dict(_pack_leaf(wt, cfg))
            leaf["cfg"] = cfg
            info = engine.LeafInfo(k_dim=k, n_out=n, lead=(e,))
            sel = engine.select_variant(cfg, info, backend="interpret").name
            assert sel == EXPECTED_GROUPED[label], (label, sel)
            y_ref = jnp.matmul(x, dequant_leaf(leaf, jnp.float32, k_dim=k))
            tol = 1e-4 * max(1.0, float(jnp.max(jnp.abs(y_ref))))
            w_bytes = sum(int(leaf[key].size) for key in ("mask", "hi", "lo"))
            dense_bf16, dense_int8 = e * k * n * 2, e * k * n
            for backend, name in (("interpret", sel), ("xla", "xla:dequant")):
                reps = 1 if (backend == "interpret" and not smoke) else 3
                t_call, y = _bench_call(dispatch_grouped, leaf, x,
                                        backend=backend, reps=reps)
                err = float(jnp.max(jnp.abs(y - y_ref)))
                rows.append({
                    "config": f"grouped_{label}", "variant": name,
                    "m": e * c, "k": k, "n": n, "lead": e,
                    "err_tol": tol,
                    "packed_bytes": w_bytes,
                    "ratio_vs_int8": w_bytes / dense_int8,
                    "ratio_vs_bf16": w_bytes / dense_bf16,
                    "proj_decode_us_bf16": dense_bf16 / HBM_BW * 1e6,
                    "proj_decode_us_strum": w_bytes / HBM_BW * 1e6,
                    "sec_per_call": t_call,
                    "tokens_per_s": e * c / t_call,
                    "max_abs_err": err,
                })
    attn_rows = run_attn_rows(smoke=smoke)
    rows += attn_rows
    from benchmarks.common import write_report
    write_report("kernel_bench", rows, smoke=smoke)
    write_report("BENCH_decode_attention", attn_rows, smoke=smoke,
                 interpret=jax.default_backend() != "tpu")
    print("name,us_per_call,derived")
    for r in rows:
        print(f"kernel/{r['config']}/{r['variant']}_"
              f"{r['m']}x{r['k']}x{r['n']},"
              f"{r['sec_per_call']*1e6:.0f},"
              f"tok_s={r['tokens_per_s']:.1f};"
              f"hbm_us_proj={r['proj_decode_us_strum']:.1f};"
              f"vs_bf16=x{r['ratio_vs_bf16']:.4f};err={r['max_abs_err']:.2e}")
    bad = [r for r in rows if r["max_abs_err"] > r["err_tol"]]
    assert not bad, f"variant disagreement vs oracle: {bad[:3]}"
    return rows


# sharded-mode shapes: (K, N, pattern) — block axis must divide the FSDP
# axis (4) and K the TP axis for 'row'
SHARDED_SHAPES = [(2048, 4096, "col"), (4096, 2048, "row")]
SMOKE_SHARDED_SHAPES = [(256, 512, "col"), (512, 256, "row")]


def run_sharded(smoke: bool = False):
    """Benchmark the sharded:* family on a forced 8-device host mesh."""
    n_dev = len(jax.devices())
    assert n_dev >= 8, (
        f"--sharded needs 8 host devices, found {n_dev}; run with "
        f"XLA_FLAGS=--xla_force_host_platform_device_count=8 (the __main__ "
        f"block sets it, so jax was initialized before main() ran)")
    from repro.engine.dispatch import dequant_leaf, dispatch
    from repro.models.quantize import _pack_leaf
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    shapes = SMOKE_SHARDED_SHAPES if smoke else SHARDED_SHAPES
    smoke_labels = ("mip2q_p0.5", "dliq_p1.0", "dliq_p0.0")
    configs = [c for c in CONFIGS if c[0] in smoke_labels] if smoke \
        else CONFIGS
    rows = []
    for label, cfg in configs:
        for (k, n, pattern) in shapes:
            if k % cfg.w:
                continue
            wt = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
            leaf = dict(_pack_leaf(wt, cfg))
            leaf["cfg"] = cfg
            info = engine.LeafInfo(k_dim=k, n_out=n, fsdp=("data",),
                                   tp_pattern=pattern)
            x = jnp.asarray(rng.normal(size=(8, k)).astype(np.float32))
            sel = engine.select_variant(cfg, info, backend="interpret").name
            if smoke:
                # acceptance: a packed FSDP leaf under a pallas-family
                # backend selects the compressed-gather pallas path
                assert sel == "sharded:gather_pallas", (label, sel)
            want = x @ dequant_leaf(leaf, jnp.float32, cfg=cfg, k_dim=k)
            tol = 1e-4 * max(1.0, float(jnp.max(jnp.abs(want))))
            payload = int(sum(leaf[key].size for key in ("mask", "hi", "lo")))
            dense_bytes = engine.dense_gather_bytes(k, n, jnp.bfloat16)
            for backend, name in (("interpret", sel),
                                  ("xla", "sharded:gather_dequant")):
                fn = lambda l, xx, _p=pattern, _b=backend: dispatch(  # noqa: E731
                    l, xx, mesh=mesh, tp_pattern=_p, backend=_b)
                with mesh:
                    stats = telemetry.all_gather_stats(fn, leaf, x, mesh=mesh)
                    reps = 1 if backend == "interpret" and not smoke else 3
                    t_call, y = _bench_call(fn, leaf, x, reps=reps)
                err = float(jnp.max(jnp.abs(y - want)))
                assert err < tol, (label, name, pattern, err, tol)
                rows.append({
                    "config": f"sharded_{label}", "variant": name,
                    "pattern": pattern, "m": 8, "k": k, "n": n,
                    "packed_bytes": payload,
                    "gathered_bytes": stats["global_operand_bytes"],
                    "dense_gather_bytes": dense_bytes,
                    "gather_ratio_vs_bf16":
                        stats["global_operand_bytes"] / dense_bytes,
                    "sec_per_call": t_call,
                    "tokens_per_s": 8 / t_call,
                    "max_abs_err": err,
                })
    from benchmarks.common import write_report
    write_report("kernel_bench_sharded", rows, smoke=smoke)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"kernel/{r['config']}/{r['variant']}_{r['pattern']}_"
              f"{r['m']}x{r['k']}x{r['n']},"
              f"{r['sec_per_call']*1e6:.0f},"
              f"tok_s={r['tokens_per_s']:.1f};"
              f"gathered={r['gathered_bytes']};"
              f"vs_dense_gather=x{r['gather_ratio_vs_bf16']:.4f};"
              f"err={r['max_abs_err']:.2e}")
    # the whole point: the wire moves the packed payload, not dense bytes
    bad = [r for r in rows if r["gathered_bytes"] >= r["dense_gather_bytes"]]
    assert not bad, f"sharded gather moved dense-scale bytes: {bad[:3]}"
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + config subset (CI interpret mode)")
    ap.add_argument("--check-only", action="store_true",
                    help="only assert plan/variant selection, no timing")
    ap.add_argument("--sharded", action="store_true",
                    help="benchmark the sharded:* family on a forced "
                         "8-device host mesh")
    args = ap.parse_args()
    if args.sharded and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # must land before jax initializes its backend (lazy: nothing above
        # touches devices at import time)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8"
                                   ).strip()
    if args.check_only:
        check_selection()
    elif args.sharded:
        run_sharded(smoke=args.smoke)
    else:
        run(smoke=args.smoke)
