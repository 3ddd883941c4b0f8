"""CPU rehearsal of a whole run: the harness, minus its look for a chip,
drives ``BatchScheduler`` (interpret-mode Pallas) through the closed loop,
the metric arithmetic and the check, at smoke size.  Nothing here is a
device number.

The same run with the timed path broken underneath must come out not
correct, once for each fault a one-chip serving cell can have, and so must
the control (the reference in fp8) put in the served tokens' place.
"""
import json
import os

import jax.numpy as jnp
import pytest

from bench.lib import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
SECONDS = 4.0


def _cell(name, per_layer=False):
    def load(f):
        with open(os.path.join(DATA, f)) as fh:
            return json.load(fh)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return harness.Cell(
        f"{name}.smoke", 1, load(f"smoke_{name}.json"),
        load("smoke_traffic.json"),
        bench["per_layer"] if per_layer else [],
        [m for m in bench["end_to_end"] if "workloads" not in m])


def _run(cell, seed, **kw):
    return harness.run_cell(cell, harness.Options(
        seed=seed, seconds=SECONDS, backend="interpret", **kw))


# Both smoke models, with the control judged through the harness's own
# ``correct`` expression: its first choices in place of the served tokens.
@pytest.mark.parametrize("name,seed", [("qwen", 2 ** 31 + 101), ("olmo", 5)])
def test_rehearsal_is_correct_and_control_is_not(name, seed):
    out = _run(_cell(name), seed, control=True)
    extra = out["_extra"]
    assert out["correct"], out["check"]
    assert extra["control_correct"] is False, extra["compare"]
    assert out["failed"] == 0 and out["attempted"] > 4
    assert extra["compiles_window"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-2:] == ["check", "_extra"]


def test_traced_rehearsal_reads_host_metrics():
    out = _run(_cell("olmo", per_layer=True), 11, trace=True)
    m = out["metrics"]
    # no device here: device metrics stay silent rather than read 0
    assert {"decode_occupancy", "ttft_ticks", "decode_step_ms"} <= set(m)
    assert not {"matmul_roofline.decode", "attn_roofline.decode",
                "mfu.decode", "idle_share"} & set(m)
    assert 0 < m["decode_occupancy"]["value"] <= 100
    assert out["device"]["window_s"] > 0


def _token_altered(sched):
    """The decode lane's token is altered where it is produced."""
    orig = sched._decode

    def lane(*a):
        lg, hot = orig(*a)
        return lg.at[..., 3].add(1e4), hot
    sched._decode = lane


def _state_unchanged(sched):
    """Every step that writes the KV cache returns its state unchanged: the
    two lanes their hot tails, the sealer its page pool."""
    decode, prefill = sched._decode, sched._chunk_prefill
    sched._seal = lambda pool, k_page, v_page, page_id: pool

    def decode_lane(params, tok, pools, hot, *rest):
        return decode(params, tok, pools, hot, *rest)[0], hot

    def prefill_lane(params, tok, pools, hot, *rest):
        lg, _, kv = prefill(params, tok, pools, hot, *rest)
        return lg, hot, kv
    sched._decode, sched._chunk_prefill = decode_lane, prefill_lane


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_broken_timed_path_is_not_correct(fault):
    out = _run(_cell("qwen"), 2 ** 31 + 101, patch=fault)
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] > out["check"]["max_gap"]["limit"]
    assert jnp.isfinite(out["check"]["max_gap"]["value"])
