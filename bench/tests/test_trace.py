"""The trace reduction, on a small trace whose numbers are checked by hand.

The fixture (``data/trace_small.json``) is in the flattened form of
``bench.lib.trace.flatten``: per device plane its ops ``[instruction,
start_ns, dur_ns, program run]`` and program runs ``[name, start_ns,
dur_ns]``, and the benchmark's own host annotations.
"""
import json
import os
from types import SimpleNamespace

import pytest

from bench.lib import trace as T
from bench.lib import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")

# A prefill program [100, 400) and a decode program [420, 800) dispatched in
# one scheduler step, then an eager argmax [810, 820); window [0, 1000).
PRE, DEC = "jit_step(11)", "jit_step(22)"
SYNTH = {
    "device": [{"name": "/device:TPU:0", "modules": [
        [PRE, 100, 300], [DEC, 420, 380], ["jit_argmax(33)", 810, 10]],
        "ops": [
        ["strum_onehot.1", 100, 50, PRE],
        ["strum_paged_attention.2", 160, 100, PRE],
        ["fusion.3", 270, 30, PRE],
        ["while.7", 420, 370, DEC],
        ["strum_onehot.1", 420, 200, DEC],
        ["strum_paged_attention.2", 630, 150, DEC],
        ["fusion.9", 785, 10, DEC],
        ["reduce.1", 810, 10, "jit_argmax(33)"]]}],
    "host": [["bench:anchor", 0, 1]],
}
NAMES = {"strum_onehot.1": "jit(step)/while/body/pallas:onehot/strum:onehot",
         "strum_paged_attention.2":
             "jit(step)/while/body/attn:fused/strum:paged_attention",
         "fusion.9": "jit(step)/argmax", "while.7": "jit(step)/while"}
HOST = [("sched:step", 50, 900), ("sched:prefill_chunk", 60, 90),
        ("sched:decode", 350, 830)]


def test_busy_union_and_gaps():
    busy = T.union(T.op_intervals(SYNTH["device"][0]), 0, 1000)
    # 50 + 100 + 30 + [420, 795) (the loop holds the decode kernels, the
    # argmax fusion overlaps its end) + 10
    assert T.covered(busy) == 565
    assert T.busy_ns(SYNTH, 0, 1000) == 565
    assert T.busy_ns(SYNTH, 200, 700) == 60 + 30 + 280
    idle = T.gaps(busy, 0, 1000)
    assert [e - s for s, e in idle] == [100, 10, 10, 120, 15, 180]


def test_scope_sums():
    ops = SYNTH["device"][0]["ops"]
    assert T.scope_ns(ops, NAMES, ("pallas", "xla"), 0, 1000) == 250
    assert T.scope_ns(ops, NAMES, ("attn",), 0, 1000) == 250
    assert T.scope_ns(ops, NAMES, ("attn",), 400, 1000) == 150
    assert T.scopes(NAMES["strum_onehot.1"]) == ["pallas:onehot",
                                                 "strum:onehot"]
    labels = {DEC: ("decode", NAMES)}
    # the loop that holds the kernels is not an op of its own here
    assert T.top_ops(SYNTH, 0, 1000, labels, n=3) == [
        ["decode/strum:onehot/strum_onehot.1", 200 / 1e9],
        ["decode/strum:paged_attention/strum_paged_attention.2", 150 / 1e9],
        ["jit_step/strum_paged_attention.2", 100 / 1e9]]


def test_program_match_and_hlo_scopes():
    hlo = "\n".join([
        "HloModule jit_step",
        '  %strum_onehot.1 = f32[16,2048]{1,0} custom-call(), '
        'metadata={op_name="jit(step)/pallas:onehot/strum:onehot" '
        'source_file="x.py"}',
        '  ROOT %fusion.9 = s32[16]{0} fusion(), kind=kLoop, '
        'metadata={op_name="jit(step)/argmax"}',
        '  %strum_paged_attention.2 = f32[1]{0} custom-call(), '
        'metadata={op_name="jit(step)/attn:fused/strum:paged_attention"}',
        '  %while.7 = (s32[]) while(), metadata={op_name="jit(step)/while"}'])
    names = T.hlo_scopes(hlo)
    assert names["fusion.9"] == "jit(step)/argmax"
    assert T.in_families(names["strum_onehot.1"], ("pallas",))
    assert T.match_program(SYNTH["device"][0], names) == (DEC, 1.0)
    # a recompile that renumbers a kernel still finds its scope
    base = T.by_base(names)
    assert T.stack_of(base, "strum_onehot.99") == "pallas:onehot/strum:onehot"
    assert T.stack_of(base, "fusion.99") == ""


def test_gap_attribution():
    busy = T.union(T.op_intervals(SYNTH["device"][0]), 0, 1000)
    labels = T.label_gaps(T.gaps(busy, 0, 1000), HOST, n=3)
    # the longest gap falls after every span; then [300, 420) inside the
    # decode span; then [0, 100), whose midpoint the step span covers
    assert labels == [["host:untraced", 180 / 1e9],
                      ["sched:decode", 120 / 1e9],
                      ["sched:step", 100 / 1e9]]


def _reading(trace, lanes, instrs=None, loop=None):
    cell = SimpleNamespace(traffic={"n_slots": 16}, config={})
    loop = loop or SimpleNamespace(recs=[], steps=[])
    return tracing.Reading(cell=cell, loop=loop, spans=[],
                           events={}, trace=trace, offset_ns=0.0, p0=0.0,
                           p1=1000e-9, t_open=0.0, t_close=1000e-9,
                           peaks=None, lanes=lanes, instrs=instrs)


def test_program_cover():
    dev = SYNTH["device"][0]
    held = set(NAMES) | {"copy.4", "bitcast.5"}
    assert T.cover(dev, DEC, held) == (1.0, [])
    assert T.cover(dev, DEC, held - {"fusion.9"}) == (0.75, ["fusion.9"])
    hlo = "\n".join(["HloModule jit_step",
                     "  %copy.4 = f32[2]{0} copy(f32[2]{0} %p)",
                     "  ROOT %fusion.9 = s32[16]{0} fusion(), metadata={"
                     'op_name="jit(step)/argmax"}'])
    assert T.hlo_instructions(hlo) == {"copy.4", "fusion.9"}


def test_decode_lane_that_matches_no_run_is_an_error():
    """A decode step ran in the profile: its program must be found, or the
    traced run fails rather than reading rooflines off another program."""
    loop = SimpleNamespace(recs=[SimpleNamespace(uid=0, prompt=[1] * 4)],
                           steps=[(50e-9, 900e-9, [(0, 1)])])
    r = _reading(SYNTH, {"decode": NAMES}, {"decode": set(NAMES)}, loop)
    assert r.programs["decode"] == DEC
    with pytest.raises(RuntimeError, match="decode lane"):
        _reading(SYNTH, {"decode": NAMES},
                 {"decode": set(NAMES) - {"fusion.9"}}, loop)
    # no decode step in the profile: nothing to read, and no error
    assert _reading(SYNTH, {"decode": NAMES}, {"decode": set()}) \
        .programs["decode"] == ""


def test_decode_device_time_of_one_step():
    r = _reading(SYNTH, {"decode": NAMES})
    calls = [(50e-9, 900e-9, [100])]
    assert r.lane_device_ns(calls, tracing.MATMUL_FAMILIES) == (calls, 200)
    assert r.lane_device_ns(calls, tracing.ATTN_FAMILIES) == (calls, 150)
    # a step that holds no run of the decode program reads nothing
    assert r.lane_device_ns([(0, 90e-9, [1])], ("attn",)) == ([], 0.0)
    assert r.busy_s == pytest.approx(565e-9)


def test_recorded_trace():
    """One decode step of ``olmo_1b.chat`` on a TPU v5e, as traced (the
    expected numbers were computed from the raw events independently)."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        rec = json.load(f)
    tr, want, names = rec["trace"], rec["expected"], rec["decode_names"]
    lo, hi = rec["window_ns"]
    dev = tr["device"][0]
    assert T.busy_ns(tr, lo, hi) == pytest.approx(want["busy_ns"])
    assert T.match_program(dev, names) == (rec["decode_program"], 1.0)
    assert T.scope_ns(dev["ops"], names, tracing.MATMUL_FAMILIES, lo, hi) \
        == pytest.approx(want["matmul_ns"])
    assert T.scope_ns(dev["ops"], names, tracing.ATTN_FAMILIES, lo, hi) \
        == pytest.approx(want["attn_ns"])
    # the packed linears of 16 layers x 7 leaves, one attention per layer
    assert want["kernel_calls"] == {"strum_onehot": 112,
                                    "strum_paged_attention": 16}
    r = _reading(tr, {"decode": names})
    r.ns = lambda t: t      # the fixture's step is given in ns
    run = [m for m in dev["modules"] if m[0] == rec["decode_program"]][0]
    calls = [(lo, hi, [1])]
    got, ns = r.lane_device_ns(calls, tracing.ATTN_FAMILIES)
    assert got == calls and ns == pytest.approx(want["attn_ns"])
    assert run[2] > ns
