"""Fused paged attention: cache:attn_* selection, kernel parity, and page
boundaries.

Acceptance (ISSUE 9): the packed-codec decode lane selects
``cache:attn_fused`` under a pallas-family backend; the fused kernel's
sealed partial agrees with the unfused gather-then-einsum partial and with
a dense softmax oracle over the decoded pages — including the
``cache_len % page_size == 0`` boundary, unassigned ``-1`` pages, and a
doctored pool where tail and sealed page disagree; and the fused scheduler
reproduces the unfused scheduler's teacher-forced tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.policy import StruMConfig
from repro.data.pipeline import DataConfig, global_batch
from repro.engine import cache as ec
from repro.engine.registry import LeafInfo, select_variant
from repro.launch.steps import make_train_step
from repro.models import model_defs
from repro.models.attention import _merge_partials
from repro.models.params import init_params
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.serving import BatchScheduler, Request

RNG = np.random.default_rng(11)

PACKED_CODECS = [
    ("dliq_q4", StruMConfig(method="dliq", p=0.5, q=4)),
    ("mip2q_L7", StruMConfig(method="mip2q", p=0.5, L=7)),
    ("sparsity", StruMConfig(method="sparsity", p=0.5)),
]

PS, KV, HD = 16, 2, 16
FEAT = KV * HD


def _pool(cfg, n_pages, feat=FEAT):
    pages = RNG.normal(size=(n_pages, PS, feat)).astype(np.float32)
    enc = jax.vmap(lambda pg: ec.encode_page(pg, cfg))(jnp.asarray(pages))
    return pages, enc


def _specs(cfg, feat=FEAT):
    fused = ec.build_cache_spec(cfg, page_size=PS, feat=feat,
                                backend="interpret")
    unfused = ec.build_cache_spec(cfg, page_size=PS, feat=feat,
                                  backend="xla")
    return fused, unfused


def _decode_pool(enc, cfg, kv=KV):
    """(n_pages, PS, kv, HD) fp reference content of the sealed pages."""
    spec = ec.build_cache_spec(cfg, page_size=PS, feat=kv * HD,
                               backend="xla")
    dec = np.asarray(ec.decode_pages(enc, spec))
    return dec.reshape(dec.shape[0], PS, kv, HD)


def _oracle_partial(deck, decv, qf, table, n_valid):
    """Dense numpy softmax partial over the sealed, assigned pages."""
    b, kv, rep, hd = qf.shape
    acc = np.zeros((b, kv, rep, hd), np.float32)
    m = np.full((b, kv, rep), -1e30, np.float32)
    l = np.zeros((b, kv, rep), np.float32)
    for i in range(b):
        live = [int(table[i, j]) for j in range(int(n_valid[i]))
                if table[i, j] >= 0]
        if not live:
            continue
        ks = np.concatenate([deck[p] for p in live])
        vs = np.concatenate([decv[p] for p in live])
        for g in range(kv):
            sc = qf[i, g] @ ks[:, g].T                     # (rep, n*PS)
            m[i, g] = sc.max(axis=-1)
            p = np.exp(sc - m[i, g][:, None])
            l[i, g] = p.sum(axis=-1)
            acc[i, g] = p @ vs[:, g]
    return acc, m, l


# ---------------------------------------------------------------- selection --

def test_attn_variant_selection():
    """Packed codecs under a pallas-family backend select the fused kernel;
    p=1.0 upgrades to maskfree; fp passthrough and xla fall back unfused."""
    for _, cfg in PACKED_CODECS:
        fused, unfused = _specs(cfg)
        assert fused.attn_variant == "cache:attn_fused", cfg
        assert unfused.attn_variant == "cache:attn_unfused", cfg
    dense = StruMConfig(method="dliq", p=1.0, q=4)
    assert _specs(dense)[0].attn_variant == "cache:attn_fused_maskfree"
    fp = ec.build_cache_spec(None, page_size=PS, feat=FEAT,
                             backend="interpret")
    assert fp.attn_variant == "cache:attn_unfused"


def test_attn_partition_is_disjoint():
    """attn=True and attn=False contexts never see each other's variants."""
    cfg = PACKED_CODECS[0][1]
    attn = select_variant(cfg, LeafInfo(k_dim=PS, n_out=FEAT, cache=True,
                                        attn=True), backend="interpret")
    page = select_variant(cfg, LeafInfo(k_dim=PS, n_out=FEAT, cache=True),
                          backend="interpret")
    assert attn.attn and not page.attn
    assert attn.name.startswith("cache:attn_")
    assert page.name == "cache:pallas_decode"


def test_register_attn_requires_cache():
    from repro.engine.registry import register_kernel
    with pytest.raises(ValueError, match="attn"):
        register_kernel("cache:attn_bogus", family="xla", priority=-99,
                        attn=True, cache=False,
                        supports=lambda cfg, info: False)(lambda *a, **k: None)


# ------------------------------------------------------------ kernel parity --

# Kernel grids beyond the original case, each with the (pages, KV heads)
# block forced so that a small table still spans several blocks:
# (kv heads, query rows per head, pool pages, table, n_valid, block).
GRIDS = {
    # block 1 of slot 0 holds a sealed and an unsealed page; P = 5 is not a
    # multiple of Pc = 2 and n_valid = 3 is not either
    "mixed_block": (2, 3, 6, [[0, 2, 4, 1, 3], [5, -1, -1, -1, -1]],
                    [3, 1], (2, 2)),
    # -1 ids inside the sealed range: a first block with no live page, and
    # a slot whose sealed range holds none at all
    "unassigned_live": (2, 3, 6, [[-1, -1, 2, 3], [-1, -1, -1, 5]],
                        [4, 3], (2, 1)),
    # a slot with nothing sealed beside a full one
    "empty_beside_full": (2, 3, 6, [[-1, -1, -1, -1], [0, 1, 2, 3]],
                          [0, 4], (2, 2)),
    # MHA decode: one query row per KV head, every head in one step
    "mha_r1": (4, 1, 8, [[0, 1, 2, 3, 4, 5], [6, 7, 3, -1, -1, -1]],
               [5, 3], (2, 4)),
    # GQA decode: 7 query rows per KV head
    "gqa_r7": (2, 7, 8, [[7, 6, 5, 4, 3, 2], [0, 1, -1, -1, -1, -1]],
               [6, 2], (4, 2)),
    # chunked prefill: many query rows over one slot's sealed prefix
    "prefill_r64": (2, 64, 6, [[3, 1, 4, 0, 5, -1]], [5], (4, 1)),
}

MASKFREE = StruMConfig(method="dliq", p=1.0, q=4)


@pytest.mark.parametrize("label,cfg,grid", [
    *(pytest.param(label, cfg, None, id=f"{label}-cfg{i}")
      for i, (label, cfg) in enumerate(PACKED_CODECS)),
    *(pytest.param(PACKED_CODECS[i % 3][0], PACKED_CODECS[i % 3][1], name,
                   id=f"{PACKED_CODECS[i % 3][0]}-{name}")
      for i, name in enumerate(GRIDS)),
    *(pytest.param("maskfree", MASKFREE, name, id=f"maskfree-{name}")
      for name in ("mixed_block", "unassigned_live")),
])
def test_fused_matches_unfused_and_oracle(label, cfg, grid, monkeypatch):
    """Fused kernel == unfused gather-then-einsum == dense numpy softmax
    over the decoded pages, with ragged n_valid and -1 unassigned pages;
    the ``grid`` cases also force blocks smaller than the table."""
    from repro.kernels import strum_attention
    if grid is None:
        kv, rows, n_pages = KV, 3, 6
        table, n_valid = [[0, 2, 4], [5, -1, -1]], [3, 1]
    else:
        kv, rows, n_pages, table, n_valid, block = GRIDS[grid]
        monkeypatch.setattr(strum_attention, "block_shape",
                            lambda *a, **k: block)
    feat = kv * HD
    pool = {"k": _pool(cfg, n_pages, feat)[1],
            "v": _pool(cfg, n_pages, feat)[1]}
    fused_spec, unfused_spec = _specs(cfg, feat)
    if cfg is MASKFREE:
        assert fused_spec.attn_variant == "cache:attn_fused_maskfree"
    qf = jnp.asarray(RNG.normal(size=(len(table), kv, rows, HD)),
                     jnp.float32)
    table = jnp.array(table, jnp.int32)
    n_valid = jnp.array(n_valid, jnp.int32)

    fused = ec.attn_sealed_partial(pool, qf, table, n_valid, fused_spec)
    unfused = ec.attn_sealed_partial(pool, qf, table, n_valid, unfused_spec)
    for a, b in zip(fused, unfused):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    deck = _decode_pool(pool["k"], cfg, kv)
    decv = _decode_pool(pool["v"], cfg, kv)
    o_acc, o_m, o_l = _oracle_partial(deck, decv, np.asarray(qf),
                                      np.asarray(table), np.asarray(n_valid))
    got_acc = np.asarray(fused[0])
    got_m, got_l = np.asarray(fused[1]), np.asarray(fused[2])
    np.testing.assert_allclose(got_m, o_m, rtol=1e-5, atol=1e-5)
    # normalized outputs (the merge contract) against the oracle's
    ref = o_acc / np.maximum(o_l, 1e-30)[..., None]
    got = got_acc / np.maximum(got_l, 1e-30)[..., None]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


# The serving cells' shapes: (query rows per KV head, KV heads, pages);
# head_dim 128, 16-position pages of DLIQ q=4 w=16 (mask, hi, lo rows).
CELL_SHAPES = {
    "olmo_decode": (1, 16, 128), "olmo_prefill_chunk": (128, 16, 128),
    "qwen2_decode": (7, 4, 128), "qwen2_prefill_chunk": (896, 4, 128),
}


@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_block_shape_fits_budget_and_divides_heads(cell):
    """The (pages, KV heads) block chosen from a call's shapes divides the
    heads, stays within the table and the VMEM budget it declares, and
    folds several pages and heads wherever the budget allows."""
    from repro.kernels import strum_attention as sa
    r, kv, pages = CELL_SHAPES[cell]
    rows = (2, 8, 4)
    pc, g = sa.block_shape(r, kv, 128, pages, 16, 1, rows)
    assert kv % g == 0 and 1 <= pc <= pages
    assert sa._vmem_bytes(pc, g, r, 128, 16, 1, rows) <= sa.VMEM_BUDGET
    assert sa.VMEM_BUDGET <= sa.VMEM_LIMIT
    assert pc * g >= 8
    if r < 8:                         # decode folds every head
        assert g == kv
    # a smaller budget never picks a larger block
    pc2, g2 = sa.block_shape(r, kv, 128, pages, 16, 1, rows,
                             budget=sa.VMEM_BUDGET // 4)
    assert pc2 * g2 <= pc * g


def test_fused_call_records_its_grid():
    """A traced fused call counts its grid steps and records the block it
    chose (pages and KV heads a step) inside its ``attn:fused`` span."""
    from repro import telemetry
    from repro.kernels.strum_attention import block_shape
    cfg = PACKED_CODECS[0][1]
    _, enc = _pool(cfg, n_pages=4)
    pool = {"k": enc, "v": enc}
    fused_spec, _ = _specs(cfg)
    qf = jnp.asarray(RNG.normal(size=(2, KV, 1, HD)), jnp.float32)
    table = jnp.array([[0, 1, 2], [3, -1, -1]], jnp.int32)
    with telemetry.recording() as rec:
        ec.attn_sealed_partial(pool, qf, table, jnp.array([2, 1], jnp.int32),
                               fused_spec)
    pc, g = block_shape(1, KV, HD, 3, PS, 1, (2, 8, 4))
    steps = 2 * (KV // g) * -(-3 // pc)
    assert rec.counter("attn/fused/calls") == 1
    assert rec.counter("attn/fused/grid_steps") == steps
    (grid,) = [e for e in rec.chrome_trace()["traceEvents"]
               if e["name"] == "attn:fused/grid"]
    assert grid["args"] == {"pages_per_step": pc, "heads_per_step": g,
                            "grid_steps": steps}
    (span,) = rec.spans("attn:fused")
    assert span["ts"] <= grid["ts"] <= span["ts"] + span["dur"]


def test_empty_sealed_prefix():
    """n_valid == 0 (nothing sealed yet): identity partial — acc 0, l 0,
    m at the NEG_INF floor — so the merge reduces to the tail epilogue."""
    cfg = PACKED_CODECS[0][1]
    _, enc = _pool(cfg, n_pages=4)
    pool = {"k": enc, "v": enc}
    fused_spec, _ = _specs(cfg)
    qf = jnp.asarray(RNG.normal(size=(2, KV, 2, HD)), jnp.float32)
    table = jnp.full((2, 3), -1, jnp.int32)
    acc, m, l = ec.attn_sealed_partial(pool, qf, table,
                                       jnp.zeros((2,), jnp.int32),
                                       fused_spec)
    assert float(jnp.max(jnp.abs(acc))) == 0.0
    assert float(jnp.max(l)) == 0.0
    assert float(jnp.max(m)) < -9e29


def test_merge_at_page_boundary():
    """cache_len % page_size == 0: every sealed page participates and the
    merged (sealed + single-token tail) output equals one dense softmax
    over [pages, fresh]."""
    cfg = PACKED_CODECS[0][1]
    _, enck = _pool(cfg, n_pages=3)
    _, encv = _pool(cfg, n_pages=3)
    pool = {"k": enck, "v": encv}
    fused_spec, _ = _specs(cfg)
    b, rep = 1, 2
    qf = np.asarray(RNG.normal(size=(b, KV, rep, HD)), np.float32)
    table = jnp.array([[0, 1, 2]], jnp.int32)
    n_valid = jnp.array([3], jnp.int32)           # all pages sealed

    sealed = ec.attn_sealed_partial(pool, jnp.asarray(qf), table, n_valid,
                                    fused_spec)
    # tail partial: only the fresh token is live, so p = exp(sc - m) = 1,
    # l = 1, acc = v of that token
    kt = np.asarray(RNG.normal(size=(b, KV, HD)), np.float32)
    vt = np.asarray(RNG.normal(size=(b, KV, HD)), np.float32)
    m_t = np.einsum("bgrd,bgd->bgr", qf, kt)                    # (b,KV,rep)
    acc_t = np.broadcast_to(vt[:, :, None, :], (b, KV, rep, HD))
    tail = tuple(jnp.asarray(a) for a in (acc_t, m_t, np.ones_like(m_t)))
    merged = np.asarray(_merge_partials([sealed, tail]))

    deck = _decode_pool(enck, cfg)
    decv = _decode_pool(encv, cfg)
    ks = np.concatenate([deck[i] for i in range(3)] + [kt])   # kt: (1,KV,HD)
    vs = np.concatenate([decv[i] for i in range(3)] + [vt])
    want = np.zeros((b, KV, rep, HD), np.float32)
    for g in range(KV):
        sc = qf[0, g] @ ks[:, g].T
        p = np.exp(sc - sc.max(axis=-1, keepdims=True))
        want[0, g] = (p / p.sum(axis=-1, keepdims=True)) @ vs[:, g]
    np.testing.assert_allclose(merged, want, rtol=1e-4, atol=1e-5)


def test_sealed_page_wins_over_stale_tail():
    """Tail-overlay regression: once a page is sealed, the lane must read
    the *pool* bytes — a doctored (stale) tail holding different content
    must not leak into the sealed partial."""
    cfg = PACKED_CODECS[0][1]
    _, enc = _pool(cfg, n_pages=2)
    pool = {"k": enc, "v": enc}
    fused_spec, unfused_spec = _specs(cfg)
    qf = jnp.asarray(RNG.normal(size=(1, KV, 1, HD)), jnp.float32)
    table = jnp.array([[0, 1]], jnp.int32)
    n_valid = jnp.array([1], jnp.int32)
    want = ec.attn_sealed_partial(pool, qf, table, n_valid, fused_spec)

    # "stale tail" scenario: whatever garbage sits in unsealed pool slots
    # (page 1 here) must not change the partial while n_valid == 1
    doctored = jax.tree_util.tree_map(
        lambda a: a.at[1].set(jnp.zeros_like(a[1])), pool)
    for spec in (fused_spec, unfused_spec):
        got = ec.attn_sealed_partial(doctored, qf, table, n_valid, spec)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


# ------------------------------------------------- scheduler-level parity --

CFG = ModelConfig(name="fused_tiny", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                  remat=False, attn_chunk=32)
DATA = DataConfig(vocab_size=256, seq_len=64, global_batch=8, seed=3)


@pytest.fixture(scope="module")
def trained():
    params = init_params(model_defs(CFG), seed=0, dtype_override="float32")
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(
        CFG, AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=100)))
    for s in range(100):
        params, opt, _ = step(params, opt, global_batch(DATA, s))
    return params


def _prompts(n, lens=(8, 11)):
    rng = np.random.default_rng(7)
    return [jnp.asarray(rng.integers(0, CFG.vocab_size,
                                     size=(lens[i % len(lens)],)), jnp.int32)
            for i in range(n)]


def _drain(params, reqs, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 16)
    sched = BatchScheduler(CFG, params, **kw)
    for r in reqs:
        sched.submit(r)
    done = sched.run_to_completion(max_steps=500)
    return {r.uid: r for r in done}, sched


@pytest.mark.parametrize("codec", [StruMConfig(method="dliq", p=0.5, q=4),
                                   StruMConfig(method="mip2q", p=0.5, L=7)])
def test_fused_scheduler_teacher_forced_parity(trained, codec):
    """End-to-end: the fused decode lane reproduces the unfused lane's
    teacher-forced tokens (same packed cache, different kernel) and tracks
    the dense oracle within quantization noise."""
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(_prompts(2))]
    dense, _ = _drain(trained, reqs, prefill="serial")

    def forced(cache_backend):
        fr = [Request(uid=i, prompt=p, max_new_tokens=6,
                      force_tokens=dense[i].output)
              for i, p in enumerate(_prompts(2))]
        return _drain(trained, fr, kv_cache=codec, prefill="chunked",
                      cache_backend=cache_backend)

    fused, sched_f = forced("interpret")
    unfused, sched_u = forced("xla")
    assert sched_f.cache_stats()["attn_variant"] == "cache:attn_fused"
    assert sched_u.cache_stats()["attn_variant"] == "cache:attn_unfused"

    agree_fu = np.mean([np.mean(np.array(fused[i].output)
                                == np.array(unfused[i].output))
                        for i in fused])
    assert agree_fu > 0.9, agree_fu          # same math, 1e-7 reductions
    agree_dense = np.mean([np.mean(np.array(fused[i].output)
                                   == np.array(dense[i].output))
                           for i in fused])
    assert agree_dense > 0.6, agree_dense    # bounded q=4 cache noise
