"""Continuous-batching scheduler: correctness of slot-interleaved decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.configs import get_smoke_config
from repro.core.policy import StruMConfig
from repro.launch.serve import pad_caches, serve
from repro.models import model_defs
from repro.models.params import init_params
from repro.serving import BatchScheduler, Request


def _model(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = init_params(model_defs(cfg), seed=0, dtype_override="float32")
    return cfg, params


@pytest.fixture(scope="module")
def setup():
    return _model("qwen2_7b")


def _reference_tokens(cfg, params, prompt, n):
    toks, _, _ = serve(cfg, params, prompt[None, :], n, {})
    return [int(t) for t in toks[0]]


# one family per block structure the lanes share: GQA with QKV bias, MHA
# with a non-parametric norm, MoE, SSM, and the SSM + attention + MoE hybrid
@pytest.mark.parametrize("arch", ["qwen2_7b", "olmo_1b", "qwen3_moe_235b_a22b",
                                  "mamba2_780m", "jamba_1_5_large_398b"])
def test_batched_matches_sequential(arch):
    """Interleaved slot decoding == one-at-a-time serving, per request.

    ``prefill="serial"`` pins the monolithic prefill executable (identical
    math to :func:`repro.launch.serve.serve`), so the paged fp cache must
    reproduce the dense-cache token stream *exactly*; the chunked lane is
    compared teacher-forced in tests/test_serving_runtime.py (its online
    prefill attention is a different float reduction).
    """
    cfg, params = _model(arch)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8 + i,)),
                           jnp.int32) for i in range(3)]
    sched = BatchScheduler(cfg, params, n_slots=2, max_len=64,
                           prefill="serial")
    for i, pr in enumerate(prompts):
        sched.submit(Request(uid=i, prompt=pr, max_new_tokens=6))
    done = sched.run_to_completion(max_steps=200)
    assert len(done) == 3
    by_uid = {r.uid: r for r in done}
    for i, pr in enumerate(prompts):
        want = _reference_tokens(cfg, params, pr, 5)
        assert by_uid[i].output[:6] == want[:6], (i, by_uid[i].output, want)


def test_slot_recycling(setup):
    """More requests than slots: slots are reused, all finish."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    sched = BatchScheduler(cfg, params, n_slots=2, max_len=48)
    for i in range(5):
        pr = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(6,)), jnp.int32)
        sched.submit(Request(uid=i, prompt=pr, max_new_tokens=4))
    done = sched.run_to_completion(max_steps=300)
    assert sorted(r.uid for r in done) == [0, 1, 2, 3, 4]
    assert all(len(r.output) == 4 for r in done)


def test_scheduler_with_strum_compressed_weights(setup):
    """The full paper deployment: compressed weights under the scheduler."""
    cfg, params = setup
    scfg = StruMConfig(method="mip2q", p=0.5, L=7)
    qcfg = dataclasses.replace(cfg, strum=scfg)
    served = engine.build_plan(params, cfg=scfg).params
    rng = np.random.default_rng(2)
    pr = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8,)), jnp.int32)

    # untrained logits are near-uniform, so greedy token streams are a
    # chaotic map — compare the scheduler's machinery instead: compressed
    # weights run end-to-end and produce finite outputs of the right length,
    # and the first-step next-token distribution matches the dense one.
    sq = BatchScheduler(qcfg, served, n_slots=1, max_len=48)
    sq.submit(Request(uid=0, prompt=pr, max_new_tokens=5))
    got = sq.run_to_completion(max_steps=100)[0]
    assert len(got.output) == 5
    assert all(0 <= t < cfg.vocab_size for t in got.output)

    from repro.models import prefill
    lg_d, _ = prefill(params, {"tokens": pr[None]}, cfg)
    lg_q, _ = prefill(served, {"tokens": pr[None]}, qcfg)
    tv = 0.5 * float(jnp.sum(jnp.abs(
        jax.nn.softmax(lg_d[0, -1]) - jax.nn.softmax(lg_q[0, -1]))))
    assert tv < 0.1, tv
