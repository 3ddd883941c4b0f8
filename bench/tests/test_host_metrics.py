"""The per-layer metrics that read the scheduler's tick phases, page counts
and lane programs, on a synthetic reading whose numbers are checked by hand,
on a reading of a program that has none of them, and in a traced CPU
rehearsal."""
import json
import os
from types import SimpleNamespace

import pytest

from bench.lib import harness, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = ("prefill_chunk_ms", "seal_ms_per_page", "attn_page_use",
       "tick_host_ms")

# Window [0, 1000) ns.  Two prefill chunks (200 and 140 ns) and three seals
# (20, 30 and 40 ns) start inside it; one of each starts outside.
PRE, DEC, SEAL = ("jit_prefill_chunk_step(11)", "jit_decode_step(22)",
                  "jit_seal(33)")
TRACE = {"device": [{"name": "/device:TPU:0", "ops": [], "modules": [
    [SEAL, -50, 40], [PRE, 100, 200], [SEAL, 300, 20], [SEAL, 320, 30],
    [DEC, 360, 20], [PRE, 500, 140], [SEAL, 700, 40], [PRE, 1200, 999]]}],
    "host": [["bench:anchor", 0, 1]]}


def _span(name, t0, t1, **args):
    return {"name": name, "start": t0 * 1e-9, "end": t1 * 1e-9,
            "args": args}


# Two ticks: [50, 450) waits 10 + 40 ns on the device, [460, 900) 50 ns.
SPANS = [
    _span("sched:step", 50, 450, tick=0),
    _span("sched:prefill_chunk", 60, 190, slot=0, start=0, valid=16, uid=3),
    _span("sched:sync", 200, 210),
    _span("sched:seal", 290, 295, slot=0, page=4, uid=3, calls=2),
    _span("sched:decode", 355, 420, n_active=2, pages_gathered=48,
          pages_valid=12),
    _span("sched:sync", 380, 420),
    _span("sched:step", 460, 900, tick=1),
    _span("sched:decode", 700, 850, n_active=4, pages_gathered=48,
          pages_valid=30),
    _span("sched:sync", 800, 850),
    _span("sched:seal", 860, 870, slot=1, page=5, uid=4, calls=2),
]

# What a program without the phase spans, page counts and lane names shows.
OLD_TRACE = {"device": [{"name": "/device:TPU:0", "ops": [], "modules": [
    ["jit_step(11)", 100, 200], [SEAL, 300, 20]]}], "host": []}
OLD_SPANS = [
    _span("sched:step", 50, 450, tick=0),
    _span("sched:prefill_chunk", 60, 190, slot=0, start=0, valid=16),
    _span("sched:seal", 290, 295, slot=0, page=4),
    _span("sched:decode", 355, 420, n_active=2),
]


def _reading(trace, spans):
    cell = SimpleNamespace(traffic={"n_slots": 16}, config={})
    return tracing.Reading(cell=cell, loop=SimpleNamespace(recs=[], steps=[]),
                           spans=spans, events={}, trace=trace,
                           offset_ns=0.0, p0=0.0, p1=1000e-9, t_open=0.0,
                           t_close=1000e-9, peaks=None)


def _read(name, r):
    return harness.metric_reader(name).read(r)


@pytest.mark.parametrize("name,want", [
    ("prefill_chunk_ms", (200 + 140) / 2 / 1e6),
    ("seal_ms_per_page", (20 + 30 + 40) / 3 * 2 / 1e6),
    ("attn_page_use", 100.0 * (12 + 30) / (48 + 48)),
    ("tick_host_ms", ((400 - 10 - 40) + (440 - 50)) / 2 / 1e6),
])
def test_metric_by_hand(name, want):
    assert _read(name, _reading(TRACE, SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_metric_silent_without_its_source(name):
    # a program without the new spans, arguments and program names
    assert _read(name, _reading(OLD_TRACE, OLD_SPANS)) is None
    # no device plane (the CPU rehearsal): the device metrics fall silent
    no_device = _reading({"device": [], "host": []}, SPANS)
    if name in ("prefill_chunk_ms", "seal_ms_per_page"):
        assert _read(name, no_device) is None
    else:
        assert _read(name, no_device) == _read(name, _reading(TRACE, SPANS))
    assert _read(name, _reading({"device": [], "host": []}, [])) is None


def test_entries_name_their_readers():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == ["olmo_1b.chat"]
        assert (harness.ROOT / "bench" / "metrics" / f"{name}.py").exists()


def test_traced_rehearsal_reads_tick_metrics():
    def load(f):
        with open(os.path.join(DATA, f)) as fh:
            return json.load(fh)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.Cell("olmo.smoke", 1, load("smoke_olmo.json"),
                        load("smoke_traffic.json"), bench["per_layer"],
                        [m for m in bench["end_to_end"]
                         if "workloads" not in m])
    out = harness.run_cell(cell, harness.Options(
        seed=2 ** 31 + 13, seconds=4.0, backend="interpret", trace=True))
    m = out["metrics"]
    assert {"attn_page_use", "tick_host_ms"} <= set(m)
    # no device here: the device trace's program runs are not read
    assert not {"prefill_chunk_ms", "seal_ms_per_page"} & set(m)
    assert 0 < m["attn_page_use"]["value"] <= 100
    assert m["tick_host_ms"]["value"] > 0
    assert out["correct"], out["check"]
