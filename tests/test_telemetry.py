"""repro.telemetry acceptance: the disabled path leaves zero state, dispatch
counters agree with the plan's variant distribution, scheduler lifecycle
streams are well-ordered, the latency math is exact on a synthetic log, and
the exported Chrome trace round-trips through the validator CLI."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, telemetry
from repro.configs import get_smoke_config
from repro.core.policy import StruMConfig
from repro.models import model_defs
from repro.models.params import init_params
from repro.serving import BatchScheduler, Request
from repro.telemetry.recorder import _STACK, NULL_SPAN

RNG = np.random.default_rng(0)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("qwen2_7b"), dtype="float32")
    params = init_params(model_defs(cfg), seed=0, dtype_override="float32")
    return cfg, params


@pytest.fixture(autouse=True)
def _stack_balanced():
    """Every test must leave the recorder stack exactly as it found it."""
    before = list(_STACK)
    yield
    assert _STACK == before


def _hetero_schedule(params):
    from repro.autotune.schedule import StruMSchedule
    from repro.core.apply import _named_leaves
    assignments = {}
    for name, leaf in _named_leaves(params):
        if not name.endswith("/w") or not hasattr(leaf, "ndim"):
            continue
        if "/attn/" in name:
            assignments[name] = StruMConfig(method="mip2q", p=0.5, L=5, w=16)
        elif "/mlp/" in name:
            assignments[name] = StruMConfig(method="dliq", p=1.0, q=4, w=8)
    return StruMSchedule(assignments=assignments)


# ------------------------------------------------------------ disabled path

def test_disabled_recorder_is_noop():
    assert not telemetry.enabled()
    assert telemetry.current() is None
    # every hook is an early return; span hands back the shared singleton
    telemetry.inc("x", 3)
    telemetry.gauge("g", 1.0)
    telemetry.observe("h", 2.0)
    telemetry.event("e", cat="test")
    telemetry.request_event(0, "submitted")
    s = telemetry.span("a")
    assert s is telemetry.span("b", cat="other") is NULL_SPAN
    with s:
        pass
    # nothing above left state anywhere a fresh recorder could see
    with telemetry.recording() as rec:
        assert rec.empty
    assert rec.empty
    assert not telemetry.enabled()


def test_disabled_dispatch_leaves_no_state():
    """Instrumented engine code run with no recorder records nothing."""
    assert not telemetry.enabled()
    w = jnp.asarray(RNG.normal(size=(16, 8)).astype(np.float32))
    plan = engine.build_plan({"w": w}, cfg=StruMConfig(method="dliq", q=4),
                             scope="tree")
    (entry,) = plan.entries.values()
    x = jnp.asarray(RNG.normal(size=(2, 16)).astype(np.float32))
    engine.dispatch(entry.leaf, x)
    with telemetry.recording() as rec:
        assert rec.empty


def test_recorder_stack_broadcasts():
    """configure() + recording() both receive the same events."""
    outer = telemetry.configure()
    try:
        with telemetry.recording() as inner:
            telemetry.inc("k")
            with telemetry.span("s:one"):
                pass
        assert inner.counter("k") == 1
        assert outer.counter("k") == 1
        assert len(inner.spans("s:")) == len(outer.spans("s:")) == 1
    finally:
        telemetry.shutdown(outer)
    assert not telemetry.enabled()


# ------------------------------------------- dispatch counters vs the plan

def test_dispatch_counters_match_plan_distribution(setup):
    """One dispatch per plan entry yields exactly the plan's
    variant_distribution, and the packed-bytes counter is the plan's
    mask+hi+lo payload (the Eq.-1 numerator)."""
    cfg, params = setup
    plan = engine.build_plan(params, schedule=_hetero_schedule(params),
                             backend="interpret")
    summ = plan.summary()
    dist = summ["variant_distribution"]
    assert len(dist) >= 2, dist           # heterogeneous by construction
    with telemetry.recording() as rec:
        for name, entry in plan.entries.items():
            assert entry.leaf is not None, name
            lead = tuple(entry.shape[:-2])
            x = jnp.asarray(RNG.normal(size=lead + (1, entry.shape[-2]))
                            .astype(np.float32))
            engine.dispatch(entry.leaf, x)
    assert rec.counters("dispatch/variant/") == dist
    assert rec.counter("dispatch/packed_bytes") \
        == summ["packed_payload_bytes"]
    assert rec.counter("dispatch/sharded/gathered_packed_bytes") == 0


def test_dispatch_counts_grid_steps_of_packed_calls():
    """``dispatch/grid_steps`` holds the grid steps of each 2-D Pallas call
    at trace time: a 1024 x 4096 MIP2Q weight over 8 rows is four 1024 x
    1024 blocks (64 steps of a 256 x 256 grid); the XLA fallback counts
    none."""
    import jax
    from repro.core.apply import pack_array
    cfg = StruMConfig(method="mip2q", p=0.5, L=5)
    p = pack_array(jnp.asarray(RNG.normal(size=(1024, 4096))
                               .astype(np.float32)), cfg)
    leaf = {"mask": p.mask, "hi": p.hi, "lo": p.lo, "scale": p.scale}
    x = jax.ShapeDtypeStruct((8, 1024), jnp.bfloat16)
    for backend, want in (("interpret", 4), ("xla", 0)):
        with telemetry.recording() as rec:
            jax.make_jaxpr(lambda x: engine.dispatch(
                leaf, x, strum=cfg, backend=backend))(x)
        assert rec.counter("dispatch/grid_steps") == want, backend


# --------------------------------------------- scheduler lifecycle streams

def test_scheduler_lifecycle_well_ordered(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    with telemetry.recording() as rec:
        sched = BatchScheduler(cfg, params, n_slots=2, max_len=48)
        for i in range(3):
            pr = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(6 + i,)),
                             jnp.int32)
            sched.submit(Request(uid=i, prompt=pr, max_new_tokens=4))
        done = sched.run_to_completion(max_steps=200)
        st = sched.cache_stats()
    assert len(done) == 3 and st["codec"] == "cache:fp_passthrough"

    log = rec.request_log()
    assert set(log) == {0, 1, 2}
    for uid, events in log.items():
        telemetry.check_well_ordered(events)
        stages = [s for s, _, _ in events]
        for want in ("submitted", "admitted", "prefill", "first_token",
                     "retired"):
            assert want in stages, (uid, stages)

    lat = rec.latency_summary()
    assert lat["n_requests"] == lat["n_retired"] == 3
    assert lat["good_tokens"] == 12           # 3 requests x 4 tokens
    assert lat["ttft_p50_us"] > 0 and lat["goodput_tok_s"] > 0

    c = rec.counters()
    assert c["sched/submitted"] == c["sched/admitted"] == 3
    assert c["sched/retired"] == 3
    assert c["sched/ticks"] == sched._steps
    assert c["pages/alloc"] > 0 and c["pages/freed"] > 0
    assert rec.spans("sched:step"), "scheduler step spans missing"
    assert rec.spans("sched:prefill"), "prefill spans missing"
    assert rec.spans("sched:decode"), "decode spans missing"
    assert rec.gauge_series("sched/queue_depth"), "queue-depth gauge missing"
    assert rec.gauge_series("pages/in_use"), "page occupancy gauge missing"
    g = rec.gauges()
    assert g["cache/resident_packed_bytes"] == 0      # fp passthrough cache
    assert g["cache/resident_fp_bytes"] > 0
    assert g["cache/ratio_vs_int8"] == st["ratio_vs_int8"]


def test_check_well_ordered_rejects_bad_streams():
    with pytest.raises(ValueError, match="before 'first_token'"):
        telemetry.check_well_ordered([("token", 0.0, {})])
    with pytest.raises(ValueError, match="out of order"):
        telemetry.check_well_ordered([("admitted", 0.0, {}),
                                      ("submitted", 1.0, {})])
    with pytest.raises(ValueError, match="regressed"):
        telemetry.check_well_ordered([("submitted", 5.0, {}),
                                      ("admitted", 1.0, {})])
    with pytest.raises(ValueError, match="unknown"):
        telemetry.check_well_ordered([("warp", 0.0, {})])
    # stage skipping is legal (zero-budget submitted->retired)
    telemetry.check_well_ordered([("submitted", 0.0, {}),
                                  ("retired", 1.0, {})])


# ------------------------------------------------------------ latency math

def test_latency_summary_synthetic_log():
    log = {
        1: [("submitted", 0.0, {}), ("admitted", 10.0, {}),
            ("prefill", 20.0, {}), ("first_token", 100.0, {}),
            ("decode", 100.0, {}), ("token", 150.0, {}),
            ("token", 250.0, {}), ("retired", 250.0, {})],
        2: [("submitted", 0.0, {}), ("first_token", 200.0, {}),
            ("retired", 200.0, {})],
    }
    m = telemetry.request_metrics(log)
    assert m[1]["ttft_us"] == 100 and m[1]["queue_us"] == 10
    assert m[1]["e2e_us"] == 250 and m[1]["n_tokens"] == 3
    assert m[1]["token_intervals_us"] == [50, 100]
    assert m[2]["n_tokens"] == 1 and m[2]["token_intervals_us"] == []

    s = telemetry.latency_summary(log)
    assert s["n_requests"] == s["n_retired"] == 2
    assert s["ttft_p50_us"] == pytest.approx(150.0)    # median of 100, 200
    assert s["ttft_p99_us"] == pytest.approx(199.0)
    assert s["tok_p50_us"] == pytest.approx(75.0)      # median of 50, 100
    assert s["good_tokens"] == 4
    assert s["wall_us"] == 250
    assert s["goodput_tok_s"] == pytest.approx(4 / 250e-6)

    assert telemetry.percentile([], 50) is None
    assert telemetry.percentile([7.0], 99) == 7.0


# ------------------------------------------------- trace export + validator

def test_trace_export_validator_and_cli(tmp_path):
    p = tmp_path / "trace.json"
    with telemetry.recording(trace_path=str(p)):
        with telemetry.span("sched:step", cat="sched", tick=0):
            pass
        with telemetry.span("cache:pallas_decode", cat="cache"):
            pass
        telemetry.inc("dispatch/packed_bytes", 128)
        telemetry.gauge("pages/in_use", 3)
        telemetry.event("page_alloc", cat="pages", n=2)
        telemetry.request_event(0, "submitted")
        telemetry.request_event(0, "first_token")
        telemetry.request_event(0, "retired")
    data = telemetry.validate_chrome_trace(str(p))
    counts = telemetry.require_spans(data, ["sched:", "cache:"])
    assert counts == {"sched:": 1, "cache:": 1}
    with pytest.raises(ValueError, match="missing required spans"):
        telemetry.require_spans(data, ["nonexistent:"])

    tele = data["strumTelemetry"]
    assert tele["counters"]["dispatch/packed_bytes"] == 128
    assert tele["gauges"]["pages/in_use"] == 3
    assert tele["latency_summary"]["n_requests"] == 1
    assert tele["dropped_events"] == 0

    from repro.telemetry import check
    assert check.main([str(p), "--require", "sched:",
                       "--require", "cache:"]) == 0
    assert check.main([str(p), "--require", "nope:"]) == 1
    assert check.main([str(tmp_path / "absent.json")]) == 1


def test_validate_chrome_trace_rejects_malformed(tmp_path):
    with pytest.raises(ValueError, match="traceEvents"):
        telemetry.validate_chrome_trace({"foo": 1})
    with pytest.raises(ValueError, match="missing phase"):
        telemetry.validate_chrome_trace({"traceEvents": [{"name": "x"}]})
    with pytest.raises(ValueError, match="'dur'"):
        telemetry.validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "x", "ts": 0}]})


# -------------------------------------------------------- deprecation shim

def test_engine_all_gather_stats_shim_removed():
    """The deprecated ``engine.all_gather_stats`` shim is gone; the
    telemetry home is the only entry point."""
    assert not hasattr(engine, "all_gather_stats")
    assert "all_gather_stats" not in engine.__all__

    def fn(x):
        return x * 2
    st = telemetry.all_gather_stats(fn, jnp.ones((4,), jnp.float32))
    assert st["ops"] == [] and st["gathered_bytes"] == 0
