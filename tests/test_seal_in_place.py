"""Sealing in place: the sealer donates its page pool, cuts the page out of
its source inside the program, and writes the same bytes the copying
``.at[:, page_id].set`` expression wrote — into the pool it was given, so a
seal costs one page and not a copy of the pool."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, telemetry
from repro.configs.base import ModelConfig
from repro.core.policy import StruMConfig
from repro.engine.cache import CACHE_PAYLOAD_KEYS, encode_page
from repro.models import model_defs
from repro.models.params import init_params
from repro.serving import BatchScheduler, Request
from repro.serving import pages as pages_mod

CFG = ModelConfig(name="seal_tiny", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                  remat=False, attn_chunk=32, dtype="float32")
PS = 16
N_PAGES = 6
DLIQ = StruMConfig(method="dliq", p=0.5, q=4)
CODECS = {"packed": DLIQ, "fp": None}


def _copying_seal(spec):
    """The copying sealer the in-place one replaced: the page is cut out on
    the host, and ``.at[:, page_id].set`` writes a new pool."""
    ps = spec.page_size

    def seal(pool, k_page, v_page, page_id):
        out = dict(pool)
        for name, page in (("k", k_page), ("v", v_page)):
            flat = page.reshape(page.shape[0], ps, -1)
            if spec.packed:
                enc = jax.vmap(lambda p: encode_page(p, spec.cfg))(
                    flat.astype(jnp.float32))
                out[name] = {k: pool[name][k].at[:, page_id].set(enc[k])
                             for k in CACHE_PAYLOAD_KEYS}
            else:
                out[name] = {"pages": pool[name]["pages"]
                             .at[:, page_id].set(flat)}
        return out
    jitted = jax.jit(seal)

    def call(pool, k_src, v_src, at):
        row, start, pid = (int(x) for x in at)
        return jitted(pool, k_src[:, row, start:start + ps],
                      v_src[:, row, start:start + ps], jnp.int32(pid))
    return call


def _spec(codec):
    return pages_mod.make_cache_spec(CFG, codec, PS)


def _random_pool(spec, seed):
    """A pool whose every byte is drawn, so that a write to the wrong page
    or a lost page shows."""
    rng = np.random.default_rng(seed)
    shapes = jax.eval_shape(lambda: pages_mod.init_pools(CFG, N_PAGES, spec))
    pos = next(k for k, v in shapes.items() if v)

    def draw(s):
        return np.frombuffer(rng.bytes(s.size * s.dtype.itemsize),
                             s.dtype).reshape(s.shape)
    return jax.tree.map(draw, shapes[pos])


def _sources(kind, seed):
    """(k_src, v_src, row, start): a hot tail tree read at a non-zero row,
    or a prefill chunk's KV window read at a non-zero start."""
    rng = np.random.default_rng(seed)
    g, kv, hd = CFG.n_layers, CFG.n_kv_heads, CFG.hd
    shape, row, start = (((g, 3, PS, kv, hd), 2, 0) if kind == "tail"
                         else ((g, 1, 3 * PS, kv, hd), 0, 2 * PS))
    k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(2))
    return k, v, row, start


def _bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("page_id", [0, N_PAGES - 1])
@pytest.mark.parametrize("kind", ["tail", "chunk"])
@pytest.mark.parametrize("codec", list(CODECS))
def test_in_place_seal_writes_the_copying_sealers_bytes(codec, kind,
                                                         page_id):
    spec = _spec(CODECS[codec])
    host = _random_pool(spec, seed=1)
    k, v, row, start = _sources(kind, seed=2)
    at = np.array([row, start, page_id], np.int32)
    want = _copying_seal(spec)(jax.tree.map(jnp.asarray, host), k, v, at)
    got = pages_mod.make_sealer(spec)(jax.tree.map(jnp.asarray, host), k, v,
                                      at)
    assert _bytes(got) == _bytes(want)
    # the other pages are the pool's own; the sealed one is new
    for leaf, before in zip(jax.tree.leaves(got), jax.tree.leaves(host)):
        keep = np.arange(N_PAGES) != page_id
        assert np.asarray(leaf)[:, keep].tobytes() == \
            before[:, keep].tobytes()


@pytest.mark.parametrize("codec", list(CODECS))
def test_sealer_donates_every_pool_leaf(codec):
    spec = _spec(CODECS[codec])
    pool = jax.tree.map(jnp.asarray, _random_pool(spec, seed=3))
    k, v, row, start = _sources("tail", seed=4)
    at = np.array([row, start, 1], np.int32)
    seal = pages_mod.make_sealer(spec)
    text = seal.lower(pool, k, v, at).compile().as_text()
    aliased = re.search(r"input_output_alias=\{ (.*?) \}", text)
    assert aliased is not None, "the sealer aliases no input to its output"
    n_leaves = len(jax.tree.leaves(pool))
    outs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliased.group(1))
    assert sorted((int(o), int(p)) for o, p in outs) == \
        [(i, i) for i in range(n_leaves)]
    out = seal(pool, k, v, at)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pool))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(out))
    assert not (k.is_deleted() or v.is_deleted())


def _serve(params, prefill, codec, copying=False, plan=None, speculative=0):
    """Chunked prefill over several pages (a 40-token prompt in 32-token
    chunks) or serial prefill, then decode across tail pages: six pages
    sealed, two from the 40-token prompt and one from the 23-token one, then
    one tail page of each request."""
    sched = BatchScheduler(CFG, params, plan=plan, n_slots=2, max_len=64,
                           page_size=PS, prefill=prefill, prefill_chunk=32,
                           kv_cache=codec, speculative=speculative)
    if copying:
        sched._seal = _copying_seal(sched.spec)
    rng = np.random.default_rng(11)
    with telemetry.recording() as rec:
        for uid, (plen, n) in enumerate(((40, 14), (5, 16), (23, 12))):
            prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, (plen,)),
                                 jnp.int32)
            sched.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n))
        done = sched.run_to_completion(max_steps=300)
    return {r.uid: list(r.output) for r in done}, rec


@pytest.fixture(scope="module")
def params():
    return init_params(model_defs(CFG), seed=0, dtype_override="float32")


@pytest.mark.parametrize("lane", ["chunked", "serial", "speculative"])
@pytest.mark.parametrize("codec", list(CODECS))
def test_scheduler_seals_every_page_in_place(params, lane, codec):
    """Every page the scheduler seals (prefill chunks, serial prefill, decode
    and verify tails) is sealed in place, and the tokens are those of the
    same run through the copying sealer."""
    kw = {"prefill": "serial" if lane == "serial" else "chunked"}
    if lane == "speculative":
        kw.update(speculative=2, plan=engine.build_plan(
            params, cfg=StruMConfig(method="dliq", w=16, p=0.5, q=4),
            float_only=True))
    got, rec = _serve(params, codec=CODECS[codec], **kw)
    sealed = rec.counter("sched/pages_sealed")
    assert sealed == 6 and rec.counter("sched/seals_in_place") == sealed
    if lane == "speculative":
        assert rec.counter("spec/rounds") > 0
    want, ref = _serve(params, codec=CODECS[codec], copying=True, **kw)
    assert got == want
    # the counter counts pools consumed, not seals: a copy is not in place
    assert ref.counter("sched/pages_sealed") == sealed
    assert ref.counter("sched/seals_in_place") == 0
