"""The plain reference against the program, on the CPU at smoke size.

The reference imports nothing of the program; these tests are where the two
meet: the codecs must give the served values bit for bit, and the whole
forward must match the program's monolithic prefill to bf16 rounding.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness
from bench.refs import dense_decoder as ref

DATA = os.path.join(os.path.dirname(__file__), "data")


def _config(name):
    with open(os.path.join(DATA, f"smoke_{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("shape", [(64, 48), (256, 40), (2048, 24)])
def test_mip2q_matches_packed_bytes(shape):
    from repro.core import packing
    from repro.core.apply import pack_array
    from repro.core.policy import StruMConfig
    w = (jax.random.normal(jax.random.PRNGKey(shape[0]), shape) * 0.05
         ).astype(jnp.bfloat16)
    w = w.at[3, 5].set(0)                       # a zero gets the +1 code
    cfg = StruMConfig(method="mip2q", w=16, p=0.5, L=5)
    served = packing.dequantize(pack_array(w, cfg), jnp.float32)
    mine = ref.mip2q_weight(w, bw=16, n_low=8, L=5)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(served))


@pytest.mark.parametrize("feat", [32, 512])
def test_dliq_pages_match_sealed_pages(feat):
    from repro.core import packing
    from repro.core.policy import StruMConfig
    from repro.engine.cache import encode_page
    cfg = StruMConfig(method="dliq", w=16, p=0.5, q=4)
    x = jax.random.normal(jax.random.PRNGKey(feat), (48, feat)) * 3.0
    mine = ref.dliq_pages(x, page=16, bw=16, n_low=8, q=4)
    for pg in range(3):
        page = x[16 * pg:16 * (pg + 1)]
        e = encode_page(page, cfg)
        p = packing.PackedStruM("dliq", 16, 8, 4, cfg.L, 16, e["scale"],
                                e["mask"], e["hi"], e["lo"])
        np.testing.assert_array_equal(
            np.asarray(mine[16 * pg:16 * (pg + 1)]),
            np.asarray(packing.dequantize(p, jnp.float32)))


@pytest.mark.parametrize("name", ["qwen", "olmo"])
def test_forward_matches_monolithic_prefill(name):
    """A prompt within one prefill chunk touches no sealed page, so the
    reference's last logits are the program's monolithic prefill over the
    same packed weights, up to bf16 activations (measured here: a relative
    L2 of a few 1e-3; the limit leaves 3x room)."""
    from repro.launch.steps import make_prefill_step
    config = _config(name)
    seed = 2 ** 31 + 7
    plan, mcfg = harness.build_plan(config, seed)
    prompt = np.random.default_rng(0).integers(0, config["vocab_size"], 24,
                                               dtype=np.int32)
    lg, _ = jax.jit(make_prefill_step(mcfg))(
        plan.params, {"tokens": jnp.asarray(prompt)[None]})
    got = np.asarray(lg[0, -1, :config["vocab_size"]], np.float32)
    want = ref.last_logits(config, seed, prompt)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    print(name, rel, want.max() - want[got.argmax()])
    assert rel < 3e-2, rel
    # near-ties may swap the first choice, but only by a near-tie's gap
    assert want.max() - want[got.argmax()] < 0.05


def test_leaves_cover_the_program_tree():
    """Every leaf the program declares is drawn, at its shape."""
    for name in ("qwen", "olmo"):
        plan, _ = harness.build_plan(_config(name), 1)
        assert set(plan.entries) == ref.packed_names(_config(name))
