"""Tracing of one scheduler tick: each lane compiles to a program of its own
name, the host phases of a tick are spans nested in ``sched:step``, the
decode span carries the pages its attention gathers against the pages that
hold sealed context, and the spans land in a ``jax.profiler`` trace."""
import dataclasses
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_smoke_config
from repro.launch import steps
from repro.models import model_defs
from repro.models.params import init_params
from repro.serving import BatchScheduler, Request
from repro.serving.pages import make_sealer

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
PHASES = ("sched:admit", "sched:inputs", "sched:sync", "sched:emit")


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("qwen2_7b"), dtype="float32")
    params = init_params(model_defs(cfg), seed=0, dtype_override="float32")
    return cfg, params


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _lower(lane, cfg, params, sched):
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    n, pp = sched.n_slots, sched.pages_per_seq
    spec, pools, hot = sched.spec, sched.pools, sched.hot
    table = sds((n, pp), i32)
    if lane == "decode":
        return jax.jit(steps.make_paged_decode_step(cfg, spec)).lower(
            params, sds((n, 1), i32), pools, hot, sds((n,), i32), table,
            sds((n,), jnp.bool_))
    if lane == "prefill_chunk":
        return jax.jit(steps.make_chunked_prefill_step(cfg, spec)).lower(
            params, sds((1, sched.prefill_chunk), i32), pools, hot, table,
            sds((), i32), sds((), i32), sds((), i32))
    if lane == "verify":
        return jax.jit(steps.make_verify_step(cfg, spec)).lower(
            params, sds((1, 3), i32), pools, hot, table, sds((), i32),
            sds((), i32))
    prefill = steps.make_prefill_step(cfg)
    batch = {"tokens": sds((1, 8), i32)}
    if lane == "prefill":
        return jax.jit(prefill).lower(params, batch)
    assert lane == "dense_decode"
    _, caches = jax.eval_shape(prefill, params, batch)
    return jax.jit(steps.make_decode_step(cfg)).lower(
        params, sds((1, 1), i32), caches, sds((), i32))


@pytest.mark.parametrize("lane", sorted(steps.LANE_PROGRAMS))
def test_lane_builder_lowers_to_its_program_name(setup, lane):
    cfg, params = setup
    sched = BatchScheduler(cfg, params, n_slots=2, max_len=48)
    assert _module_name(_lower(lane, cfg, params, sched)) \
        == steps.LANE_PROGRAMS[lane]


def test_lane_program_names_are_distinct_and_sealer_keeps_its_own(setup):
    cfg, params = setup
    names = list(steps.LANE_PROGRAMS.values())
    assert len(set(names)) == len(names) and "jit_seal" not in names
    sched = BatchScheduler(cfg, params, n_slots=2, max_len=48)
    pool = sched.pools[sched._attn_pos[0]]
    tail = sched.hot[sched._attn_pos[0]]["k_tail"]
    low = make_sealer(sched.spec).lower(pool, tail, tail,
                                        np.zeros((3,), np.int32))
    assert _module_name(low) == "jit_seal"


@pytest.mark.parametrize("arch,tied", [("olmo_1b", True),
                                       ("qwen2_7b", False)])
def test_decode_lane_scopes_its_head(arch, tied):
    """The LM head, tied or untied, compiles to instructions whose op_name
    carries ``head:dense``: the device trace attributes the head by it."""
    cfg = get_smoke_config(arch)
    assert cfg.tie_embeddings is tied
    params = init_params(model_defs(cfg), seed=0)
    assert ("lm_head" in params) is not tied
    sched = BatchScheduler(cfg, params, n_slots=2, max_len=48)
    text = _lower("decode", cfg, params, sched).compile().as_text()
    scoped = [ln for ln in text.splitlines()
              if re.search(r'op_name="[^"]*head:dense', ln)]
    # the head's matmul itself: both slots' logits over the padded vocab
    logits = f"f32[{sched.n_slots},{cfg.padded_vocab}]"
    assert any(logits in ln and " dot(" in ln for ln in scoped), scoped


def test_compile_cache_hit_keeps_its_own_scopes(tmp_path):
    """Two programs that differ only in a scope (same function name, same
    ops), compiled one after the other through a persistent compilation
    cache: the second keeps its own op_names instead of loading the
    first's from the cache."""
    code = ("import jax, jax.numpy as jnp\n"
            "import repro.engine\n"
            "s = jax.ShapeDtypeStruct((4, 8), jnp.float32)\n"
            "w = jax.ShapeDtypeStruct((8, 16), jnp.float32)\n"
            "def head(x, w):\n"
            "    return jnp.dot(x, w)\n"
            "plain = head\n"
            "def head(x, w):\n"
            "    with jax.named_scope('head:dense'):\n"
            "        return jnp.dot(x, w)\n"
            "texts = [jax.jit(f).lower(s, w).compile().as_text()\n"
            "         for f in (plain, head)]\n"
            "assert 'head:dense' not in texts[0]\n"
            "assert 'head:dense' in texts[1]\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [SRC] + os.environ.get("PYTHONPATH", "").split(os.pathsep)),
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert os.listdir(tmp_path)         # the cache was written and read


def _serve_two(cfg, params):
    """Request 0 (20 prompt tokens, 14 out) and request 1 (5 prompt tokens,
    4 out) on two slots, pages of 16 and chunks of 16."""
    rng = np.random.default_rng(5)
    sched = BatchScheduler(cfg, params, n_slots=2, max_len=48)
    for uid, (plen, n) in enumerate(((20, 14), (5, 4))):
        pr = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(plen,)),
                         jnp.int32)
        sched.submit(Request(uid=uid, prompt=pr, max_new_tokens=n))
    done = sched.run_to_completion(max_steps=100)
    assert sorted(len(r.output) for r in done) == [4, 14]
    return sched


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_tick_phases_nest_in_step_and_decode_counts_pages(setup):
    cfg, params = setup
    with telemetry.recording() as rec:
        sched = _serve_two(cfg, params)
    step = rec.spans("sched:step")
    assert [s["args"]["tick"] for s in step] == list(range(sched._steps))
    for name in PHASES:
        assert rec.spans(name), name
        for sp in rec.spans(name):
            assert any(_inside(sp, st) for st in step), (name, sp)
    # tick 0 prefills 16 of request 0's tokens, tick 1 all of request 1's,
    # tick 2 the rest of request 0's; then decode
    assert len(rec.spans("sched:admit")) == len(step)
    chunks = rec.spans("sched:prefill_chunk")
    assert [(c["args"]["uid"], c["args"]["start"]) for c in chunks] \
        == [(0, 0), (1, 0), (0, 16)]
    syncs, emits = rec.spans("sched:sync"), rec.spans("sched:emit")
    decode = rec.spans("sched:decode")
    assert len(rec.spans("sched:inputs")) == len(chunks) + len(decode)
    # one sync per decode and one per finished prompt
    assert len(syncs) == len(decode) + 2 and len(emits) == len(decode)
    for d in decode:
        inner = [s for s in syncs if _inside(s, d)]
        assert len(inner) == 1
        # the decode span still ends after the host has the tokens; the
        # per-token loop follows it
        assert d["ts"] + d["dur"] >= inner[0]["ts"] + inner[0]["dur"]
        assert not any(_inside(e, d) for e in emits)
    # request 0 seals page 0 in its first chunk and page 1 when its 32nd
    # position is committed; each page is one sealer program per pool
    seals = rec.spans("sched:seal")
    assert [s["args"]["uid"] for s in seals] == [0, 0]
    assert {s["args"]["calls"] for s in seals} == {len(sched._attn_pos)}
    assert seals[0]["ts"] < chunks[1]["ts"]
    assert any(_inside(seals[1], e) for e in emits)
    # pages gathered: 2 slots x 3 pages of the 48-token window; pages valid:
    # request 1 decodes alone from 5 positions (no sealed page), then with
    # request 0 (1 sealed page) for two ticks, then request 0 alone from 22
    # to 31 positions (1 page) and at 32 (2 pages)
    assert [d["args"]["pages_gathered"] for d in decode] == [6] * 14
    assert [d["args"]["pages_valid"] for d in decode] \
        == [0, 1, 1] + [1] * 10 + [2]
    assert [d["args"]["n_active"] for d in decode] == [1, 2, 2] + [1] * 11


def test_tick_gauges_and_variant_counter_are_gone(setup):
    cfg, params = setup
    with telemetry.recording() as rec:
        _serve_two(cfg, params)
    assert not [g for g in rec.gauges() if g.startswith("sched/lane/")]
    assert not rec.counters("attn/variant/")


def test_spans_leave_jax_unimported():
    """The trace validator's side of telemetry never loads jax: a span
    enters a profiler annotation only where jax is already imported."""
    code = ("import sys\n"
            "from repro import telemetry\n"
            "with telemetry.recording() as rec:\n"
            "    with telemetry.span('sched:step', tick=0):\n"
            "        pass\n"
            "assert [s['name'] for s in rec.spans()] == ['sched:step']\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [SRC] + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    env.pop("STRUM_TRACE", None)
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


def test_step_spans_land_in_profiler_trace(setup, tmp_path):
    cfg, params = setup
    _serve_two(cfg, params)             # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.recording() as rec:
            sched = _serve_two(cfg, params)
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    prof = jax.profiler.ProfileData.from_file(files[0])
    names = [ev.name for plane in prof.planes for line in plane.lines
             for ev in line.events]
    ticks = len(rec.spans("sched:step"))
    assert ticks == sched._steps
    assert names.count("sched:step") == ticks
    assert names.count("sched:decode") == len(rec.spans("sched:decode"))
    assert names.count("sched:sync") == len(rec.spans("sched:sync"))
