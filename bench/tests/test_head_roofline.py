"""``head_roofline.decode`` on synthetic readings whose numbers are checked
by hand: the LM head's least time over the device time of the ops under
its ``head:*`` scope in the decode calls' program, and silence where the
program has no such scope or no device was traced."""
import json
import os
from types import SimpleNamespace

import pytest

from bench.lib import harness, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
NAME = "head_roofline.decode"
PEAKS = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}
DEC = "jit_decode_step(22)"

# The decode lane's compiled instructions and their name-stacks: the head's
# matmul, a packed GEMV and an attention kernel.
HEAD = {"dot.11": "jit(decode_step)/head:dense/dot_general",
        "strum_onehot.3": "jit(decode_step)/pallas:onehot/strum:onehot",
        "fusion.7": "jit(decode_step)/attn:fused/mul"}
# The same program from a parent without the head scope.
NO_SCOPE = dict(HEAD, **{"dot.11": "jit(decode_step)/dot_general"})

# Window [0, 1000) ns.  Two decode runs, one per tick: [100, 300) with the
# head's dot taking 60 ns, [500, 700) with it taking 40 ns.
TRACE = {"device": [{"name": "/device:TPU:0", "ops": [
    ["strum_onehot.3", 110, 50, DEC], ["fusion.7", 170, 60, DEC],
    ["dot.11", 230, 60, DEC],
    ["strum_onehot.3", 510, 50, DEC], ["fusion.7", 570, 60, DEC],
    ["dot.11", 640, 40, DEC]],
    "modules": [[DEC, 100, 200], [DEC, 500, 200]]}], "host": []}

# Host ticks around the runs: request 0 (prompt 5) emits its tokens 1 and
# 2, request 1 (prompt 9) its token 1 in the first tick only.
STEPS = [(50e-9, 350e-9, [(0, 1), (1, 1)]), (450e-9, 750e-9, [(0, 2)])]


def _config():
    with open(os.path.join(DATA, "smoke_qwen.json")) as f:
        return json.load(f)


def _reading(names, trace=TRACE, peaks=PEAKS):
    cell = SimpleNamespace(traffic={"n_slots": 4}, config=_config())
    recs = [SimpleNamespace(uid=0, prompt=[0] * 5),
            SimpleNamespace(uid=1, prompt=[0] * 9)]
    return tracing.Reading(
        cell=cell, loop=SimpleNamespace(recs=recs, steps=STEPS), spans=[],
        events={}, trace=trace, offset_ns=0.0, p0=0.0, p1=1000e-9,
        t_open=0.0, t_close=1000e-9, peaks=peaks,
        lanes={"decode": names}, instrs={"decode": set(names)})


def _reader():
    return harness.metric_reader(NAME)


def test_work_by_hand():
    # smoke_qwen: d 64, vocab 256 (= its 256 padded rows), untied
    flops, nbytes = _reader().head_work(_config(), 2)
    assert flops == 2 * 2 * 64 * 256
    assert nbytes == 64 * 256 * 2 + 2 * 64 * 2 + 2 * 256 * 4


def test_roofline_by_hand():
    # HBM-bound at these sizes: each call's bytes over 819 GB/s
    least = sum((64 * 256 * 2 + m * 64 * 2 + m * 256 * 4) / 819e9
                for m in (2, 1))
    want = 100.0 * least / ((60 + 40) * 1e-9)
    assert _reader().read(_reading(HEAD)) == pytest.approx(want)
    assert 0 < want <= 100


def test_silent_without_head_scope_or_device():
    # a program whose head carries no scope (the parent): nothing to read
    assert _reader().read(_reading(NO_SCOPE)) is None
    # the CPU rehearsal: no peaks, no device plane
    assert _reader().read(_reading(HEAD, peaks=None)) is None
    assert _reader().read(_reading(HEAD, trace={"device": [], "host": []})) \
        is None


def test_entry_lists_both_cells():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry["workloads"] == ["olmo_1b.chat", "qwen2_7b.batch"]
    assert entry["moves"] == "output_tok_s"
    assert entry["layer"] == "kernels"
