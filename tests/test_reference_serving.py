"""The serving path against the plain float32 reference, logit by logit.

``BatchScheduler`` serves a few requests through chunked prefill and paged
decode (interpret-mode Pallas: packed MIP2Q weights through
``pallas:onehot``, packed DLIQ pages through ``cache:attn_fused``), and
every logit row it computes for an emitted token is compared with the row
of ``bench/refs/dense_decoder.py``'s full forward over the served sequence,
on the same seeded weights.  The reference imports nothing of the program.

The grouped-query case has 14 query heads over 2 KV heads, so 7 query rows
share each KV head: an odd count below the TPU's 8-row sublane tile, as in
Qwen2-7B (28 over 4).  The MHA case is OLMo's block (tied head,
non-parametric LayerNorm), one query row per KV head.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402
from bench.refs import dense_decoder as ref  # noqa: E402
from repro.serving import Request  # noqa: E402

SERVE = {"activations": "bfloat16",
         "weights": {"method": "mip2q", "w": 16, "p": 0.5, "L": 5},
         "kv": {"method": "dliq", "w": 16, "p": 0.5, "q": 4},
         "page_size": 16, "prefill_chunk": 32}

GQA = {   # Qwen2's block: RMSNorm, QKV bias, untied head; 7 rows per KV head
    "model_type": "qwen2", "hidden_size": 224, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 14,
    "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "vocab_size": 256,
    "reference": {"module": "dense_decoder", "norm": "rmsnorm",
                  "norm_eps": 1e-06},
    "serve": SERVE,
    "program_config": {"name": "qwen2_rep7", "n_layers": 2, "d_model": 224,
                       "n_heads": 14, "n_kv_heads": 2, "head_dim": 16,
                       "d_ff": 256, "vocab_size": 256, "qkv_bias": True,
                       "rope_theta": 1000000.0, "dtype": "bfloat16"}}

MHA = {   # OLMo's block: non-parametric LayerNorm, tied head
    "model_type": "olmo", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "vocab_size": 256,
    "reference": {"module": "dense_decoder", "norm": "layernorm_nonparam",
                  "norm_eps": 1e-05},
    "serve": SERVE,
    "program_config": {"name": "olmo_mha", "n_layers": 2, "d_model": 128,
                       "n_heads": 4, "n_kv_heads": 4, "d_ff": 256,
                       "vocab_size": 256, "norm": "nonparam",
                       "tie_embeddings": True, "rope_theta": 10000.0,
                       "dtype": "bfloat16"}}

# (prompt, new tokens): a prompt of two chunks that seals pages from the
# prefill lane, one that ends mid-page, one shorter than a page; every
# decode crosses at least one page boundary and seals from the hot tail.
REQUESTS = ((45, 24), (21, 30), (9, 26))


def _serve(config, seed):
    """Serve REQUESTS on three slots; returns ``[(prompt, output,
    logits)]`` with ``logits[t]`` the row that produced ``output[t]``."""
    plan, mcfg = harness.build_plan(config, seed, backend="interpret")
    sched = harness.make_scheduler(config, {"n_slots": 3, "max_len": 96},
                                   plan, mcfg, cache_backend="interpret")
    assert {e.variant for e in plan.entries.values()} == {"pallas:onehot"}
    assert sched.spec.attn_variant == "cache:attn_fused"
    rows = {}
    decode, prefill = sched._decode, sched._chunk_prefill

    def decode_lane(params, tok, pools, hot, cache_len, table, active):
        lg, hot = decode(params, tok, pools, hot, cache_len, table, active)
        for s in np.flatnonzero(np.asarray(active)):
            req = sched.slots[s].req
            rows[req.uid, len(req.output)] = lg[s, -1]
        return lg, hot

    def prefill_lane(params, tok, pools, hot, table, slot, start, valid):
        out = prefill(params, tok, pools, hot, table, slot, start, valid)
        req = sched.slots[int(slot)].req
        if int(start) + int(valid) == len(req.prompt):
            rows[req.uid, 0] = out[0][0, int(valid) - 1]
        return out

    sched._decode, sched._chunk_prefill = decode_lane, prefill_lane
    rng = np.random.default_rng(seed % 2 ** 32)
    reqs = []
    for uid, (plen, n) in enumerate(REQUESTS):
        prompt = rng.integers(0, config["vocab_size"], plen, dtype=np.int32)
        reqs.append(Request(uid=uid, prompt=jnp.asarray(prompt),
                            max_new_tokens=n))
        sched.submit(reqs[-1])
    sched.run_to_completion()
    vocab = config["vocab_size"]
    return [(np.asarray(r.prompt), list(r.output),
             np.stack([np.asarray(rows[r.uid, t][:vocab], np.float32)
                       for t in range(len(r.output))])) for r in reqs]


def _reference(config, seed, served, control=False):
    """Reference logits of every served position: row ``plen - 1 + t`` of
    the full forward over ``prompt + output[:-1]``."""
    seqs = [(ref._pad(np.concatenate([p, np.asarray(o[:-1], np.int32)])),
             len(p)) for p, o, _ in served]
    with jax.default_matmul_precision("highest"):
        a, xs, xc, head, fnorm = ref._forward(config, seed, seqs, control)
        out = []
        for n, ((_, plen), (_, o, _)) in enumerate(zip(seqs, served)):
            x = (xc if control else xs)[n][plen - 1:plen - 1 + len(o)]
            h = ref._norm(x, fnorm, a)
            if control:
                h = ref._rnd_fp8(h)
            out.append(np.asarray((h @ head)[:, :a.vocab]))
    return out


def _rel_errors(got, want):
    """Relative L2 error of each position's logit row."""
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


# Per position, the served logit row lies within TOL (relative L2) of the
# reference's.  bf16 keeps 8 significant bits: each rounding is within
# 2**-9, and the few dozen of them on the way through two blocks, the head
# and the bf16 hot tail read here 0.012-0.032 at worst.  fp8 keeps 4 bits
# (2**-5 a rounding): the same model with every activation in fp8 reads
# 0.074-0.16 against the same reference.  0.05 lies between the two.
TOL = 0.05


@pytest.mark.parametrize("config", [GQA, MHA], ids=["gqa_rep7", "mha"])
def test_served_logits_match_reference(config):
    seed = 2 ** 31 + 29
    served = _serve(config, seed)
    assert [len(o) for _, o, _ in served] == [n for _, n in REQUESTS]
    want = _reference(config, seed, served)
    ctl = _reference(config, seed, served, control=True)
    for (p, _, got), w, c in zip(served, want, ctl):
        rel = _rel_errors(got, w)
        assert rel.max() <= TOL, (len(p), rel.max(), np.argmax(rel))
        # the tolerance is tight enough that fp8 activations fail it
        assert np.median(_rel_errors(c, w)) > TOL, len(p)
