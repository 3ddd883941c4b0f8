"""One run of one cell: set-up, closed-loop window, per-layer reading, check.

Everything the benchmark takes from the program goes through its public
serving API: ``engine.build_plan`` packs the weights the benchmark drew,
``BatchScheduler`` serves, and ``repro.telemetry`` spans (traced runs
only) say what the scheduler was doing.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench.lib import stats
from bench.lib.traffic import Mix

ROOT = Path(__file__).resolve().parents[2]
TRACE_SECONDS = 8.0       # profiled part (the end) of a traced window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files --

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    per_layer: list          # BENCHMARK.json entries that this cell reports
    end_to_end: list


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return Cell(workload, int(w["chips"]), config, traffic,
                [m for m in bench["per_layer"] if mine(m)],
                [m for m in bench["end_to_end"] if mine(m)])


def reference_module(config: dict):
    return importlib.import_module(f"bench.refs.{config['reference']['module']}")


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- program --

def model_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**config["program_config"])


def codec(c: dict):
    from repro.core.policy import StruMConfig
    kw = {k: c[k] for k in ("method", "w", "p", "q", "L") if k in c}
    return StruMConfig(**kw)


def _nest(name: str, value) -> dict:
    out: dict = {}
    cur = out
    parts = name.split("/")
    for p in parts[:-1]:
        cur = cur.setdefault(p, {})
    cur[parts[-1]] = value
    return out


def _get(tree: dict, name: str):
    for p in name.split("/"):
        tree = tree[p]
    return tree


def build_plan(config: dict, seed: int, backend: Optional[str] = None):
    """Draw every leaf from the seed and pack the linears through
    ``engine.build_plan``, one leaf at a time, dropping each bf16 leaf
    before the next is drawn (a 7B bf16 tree does not fit beside its packed
    copy on one chip)."""
    import jax
    from repro import engine
    from repro.models import model_defs
    from repro.models.params import ParamDef

    from bench.lib import weights

    ref = reference_module(config)
    specs = {lf.name: lf for lf in ref.leaves(config)}
    packed = ref.packed_names(config)
    mcfg = model_config(config)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_defs(mcfg), is_leaf=lambda x: isinstance(x, ParamDef))
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    if set(names) != set(specs):
        raise ValueError(f"program leaves {sorted(set(names) ^ set(specs))} "
                         f"differ from the configuration's")
    for name, (_, d) in zip(names, flat):
        lf = specs[name]
        want = ((lf.layers,) if lf.layers else ()) + tuple(lf.shape)
        if tuple(d.shape) != want:
            raise ValueError(f"{name}: program shape {d.shape} != {want}")

    def size(n):
        lf = specs[n]
        return (n in packed, max(lf.layers, 1) * int(np.prod(lf.shape)))

    wcfg = codec(config["serve"]["weights"])
    values, entries = {}, {}
    for name in sorted(names, key=size, reverse=True):
        arr = weights.draw(seed, specs[name])
        if name in packed:
            part = engine.build_plan(_nest(name, arr), cfg=wcfg,
                                     backend=backend)
            if list(part.entries) != [name]:
                raise ValueError(f"{name}: plan packed {list(part.entries)}")
            entries[name] = part.entries[name]
            values[name] = _get(part.params, name)
            del part
        else:
            values[name] = arr
        del arr
    return engine.ExecutionPlan(
        entries=entries, params=treedef.unflatten([values[n] for n in names]),
        backend=backend, scope="model"), mcfg


def make_scheduler(config: dict, traffic: dict, plan, mcfg,
                   cache_backend: Optional[str] = None):
    from repro.serving import BatchScheduler
    s = config["serve"]
    return BatchScheduler(
        mcfg, None, plan=plan, n_slots=int(traffic["n_slots"]),
        max_len=int(traffic["max_len"]), page_size=int(s["page_size"]),
        prefill="chunked", prefill_chunk=int(s["prefill_chunk"]),
        kv_cache=codec(s["kv"]), cache_backend=cache_backend)


# ------------------------------------------------------------ closed loop --

@dataclasses.dataclass
class Rec:
    uid: int
    prompt: np.ndarray
    max_new: int
    submit: float
    req: object = None
    times: list = dataclasses.field(default_factory=list)
    failed: bool = False
    done: float = 0.0


class ClosedLoop:
    """Each client sends its next request as soon as its last one retires."""

    def __init__(self, sched, mix: Mix):
        self.sched = sched
        self.mix = mix
        self.next = 0
        self.live: list = []
        self.recs: list = []
        self.steps: list = []          # (t_start, t_end, [(uid, k), ...])

    def submit(self, now: float) -> None:
        from repro.serving import PagesExhausted, Request
        spec = self.mix.spec(self.next)
        self.next += 1
        ids = self.mix.prompt_ids(spec)
        rec = Rec(spec.index, ids, spec.max_new_tokens, now)
        rec.req = Request(uid=spec.index, prompt=ids,
                          max_new_tokens=spec.max_new_tokens)
        self.recs.append(rec)
        try:
            self.sched.submit(rec.req)
        except (ValueError, PagesExhausted) as e:
            log(f"submit of request {spec.index} raised: {e}")
            rec.failed = True
            return
        self.live.append(rec)

    def step(self) -> float:
        t0 = time.perf_counter()
        self.sched.step()
        now = time.perf_counter()
        new = []
        still = []
        retired = 0
        for rec in self.live:
            n = len(rec.req.output)
            for k in range(len(rec.times), n):
                rec.times.append(now)
                new.append((rec.uid, k))
            if rec.req.done:
                rec.done = now
                retired += 1
            else:
                still.append(rec)
        self.live = still
        for _ in range(retired):
            self.submit(now)
        self.steps.append((t0, now, new))
        return now


def warm_up(sched, config: dict) -> None:
    """Run every program the window will run once: a two-chunk prompt
    (sealing pages from the prefill lane) whose decode crosses a page
    (sealing from the tail), then retirement."""
    from repro.serving import Request
    s = config["serve"]
    plen = int(s["prefill_chunk"]) + 1
    req = Request(uid=-1, prompt=np.arange(plen, dtype=np.int32) % 7,
                  max_new_tokens=int(s["page_size"]) + 2)
    sched.submit(req)
    sched.run_to_completion()
    if len(req.output) != req.max_new_tokens:
        raise RuntimeError("warm-up request did not complete")


# ---------------------------------------------------------------- results --

def end_to_end(recs: list, t_open: float, t_close: float) -> dict:
    def inside(t):
        return t_open < t <= t_close
    tokens = sum(1 for r in recs for t in r.times if inside(t))
    ttft, itl = [], []
    for r in recs:
        if r.failed:
            continue
        if r.times:
            if inside(r.times[0]):
                ttft.append(r.times[0] - r.submit)
        else:
            ttft.append(t_close - r.submit)
        for a, b in zip(r.times, r.times[1:]):
            if inside(a) and inside(b):
                itl.append(b - a)
    window = t_close - t_open
    return {
        "output_tok_s": tokens / window,
        "ttft_p50_ms": (stats.percentile(ttft, 50) or 0.0) * 1e3,
        "ttft_p95_ms": (stats.percentile(ttft, 95) or 0.0) * 1e3,
        "itl_p95_ms": (stats.percentile(itl, 95) or 0.0) * 1e3,
        "samples": {"tokens": tokens, "ttft": len(ttft), "itl": len(itl),
                    "window_s": window},
    }


def log_ticks(steps: list, t_open: float, n: int = 5) -> None:
    """The window's ticks on standard error: how many, their median, and the
    longest, each with its start after the window opened and its tokens, so
    that a run that lost ticks to a stall shows where."""
    if not steps:
        return
    dur = [b - a for a, b, _ in steps]
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    top = sorted(range(len(dur)), key=lambda i: -dur[i])[:n]
    log(f"ticks: {len(dur)}, median {np.median(dur) * 1e3:.1f} ms, host gap "
        f"between ticks max {max(gaps, default=0.0) * 1e3:.1f} ms; longest: "
        + ", ".join(f"#{i} at {steps[i][0] - t_open:.2f}s "
                    f"{dur[i] * 1e3:.1f} ms ({len(steps[i][2])} tokens)"
                    for i in top))


def sample_finished(recs: list, t_open: float, seed: int, min_tokens: int,
                    max_requests: int) -> list:
    """Requests finished in the window, for the check: the longest, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens."""
    from bench.lib.traffic import seed_state
    done = [r for r in recs if r.done > t_open and not r.failed]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.req.output), r.uid))
    pick = [done.pop()]
    order = seed_state(seed, 3).permutation(len(done))
    for i in order:
        if sum(len(r.req.output) for r in pick) >= min_tokens \
                or len(pick) >= max_requests:
            break
        pick.append(done[i])
    return pick


class CompileCounter:
    """Counts the programs XLA built or took from the persistent cache
    (``backend_compile_duration`` times both) and the cache's hits, as
    ``jax.monitoring`` reports them."""

    def __init__(self):
        self.n = 0
        self.hits = 0

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory(jax) -> dict:
    """Bytes in use now and the peak since the process started, on the
    fullest chip (0 where the backend keeps no statistics)."""
    now = peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        now = max(now, int(st.get("bytes_in_use", 0)))
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"in_use": now, "peak": peak}


def device_info(jax) -> dict:
    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory(jax)["peak"]}


def judge(check_cfg: dict, max_gap: float, failed: int,
          checked_tokens: int) -> tuple:
    """The numbers compared, each beside its limit, and whether the run is
    correct.  The control is judged by this same expression."""
    limit = float(check_cfg["max_gap"])
    need = int(check_cfg["min_tokens"])
    check = {"max_gap": {"value": max_gap, "limit": limit},
             "failed": {"value": failed, "limit": 0},
             "checked_tokens": {"value": checked_tokens, "limit": need}}
    correct = max_gap <= limit and failed == 0 and checked_tokens >= need
    return check, bool(correct)


# -------------------------------------------------------------------- run --

@dataclasses.dataclass
class Options:
    seed: int
    seconds: float
    trace: bool = False
    control: bool = False           # also judge the control (calibration)
    backend: Optional[str] = None   # "interpret" for the CPU rehearsal
    keep_trace: Optional[str] = None
    patch: Optional[Callable] = None  # tests: break the timed path
    t_start: float = dataclasses.field(default_factory=time.perf_counter)


def run_cell(cell: Cell, opt: Options) -> dict:
    """Set up, measure, check.  Returns the result object (the contract's
    keys, ``check`` last) plus ``_extra`` readings for logs."""
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter.event)
    config, traffic = cell.config, cell.traffic
    check_cfg = config["check"]

    mem = {}
    plan, mcfg = build_plan(config, opt.seed, backend=opt.backend)
    mem["weights"] = memory(jax)
    sched = make_scheduler(config, traffic, plan, mcfg,
                           cache_backend=opt.backend)
    del plan
    mem["scheduler"] = memory(jax)
    if opt.patch is not None:
        opt.patch(sched)
    warm_up(sched, config)
    mem["warm"] = memory(jax)
    log(f"set-up: weights packed, scheduler warm "
        f"({time.perf_counter() - opt.t_start:.1f}s)")

    mix = Mix(traffic, config["vocab_size"], opt.seed)
    loop = ClosedLoop(sched, mix)
    now = time.perf_counter()
    for _ in range(mix.clients):
        loop.submit(now)
    first = {r.uid for r in loop.recs}
    while any(not r.times and not r.failed for r in loop.recs
              if r.uid in first):
        loop.step()
    t_open = time.perf_counter()
    setup_s = t_open - opt.t_start
    compiles_setup, hits_setup = counter.n, counter.hits
    mem["open"] = memory(jax)
    log(f"window opens after {setup_s:.1f}s of set-up")

    tracer = None
    if opt.trace:
        from bench.lib.tracing import Tracer
        tracer = Tracer(opt.keep_trace)
        tracer.start()
    loop.steps.clear()
    while True:
        t = loop.step()
        if tracer is not None:
            tracer.maybe_profile(t, t_open + opt.seconds)
        if t - t_open >= opt.seconds or not loop.live:
            break
    t_close = t
    log_ticks(loop.steps, t_open)
    compiles_window = counter.n - compiles_setup
    e2e = end_to_end(loop.recs, t_open, t_close)
    device = device_info(jax)
    mem["close"] = memory(jax)
    log("memory (bytes in use / peak so far): " + ", ".join(
        f"{k} {v['in_use']} / {v['peak']}" for k, v in mem.items()))
    per_layer, breakdown = {}, None
    if tracer is not None:
        reading = tracer.finish(loop, sched, cell, mcfg, t_open, t_close)
        device.update(busy_s=reading.busy_s, window_s=reading.window_s)
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(reading)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = reading.breakdown

    # -- check, once the window has closed and the program's state is gone
    pick = sample_finished(loop.recs, t_open, opt.seed,
                           int(check_cfg["min_tokens"]),
                           int(check_cfg["max_requests"]))
    served = [(r.prompt, list(r.req.output)) for r in pick]
    bad = [r.uid for r in loop.recs if r.done and not r.failed
           and len(r.req.output) != r.max_new]
    failed = sum(r.failed for r in loop.recs) + len(bad)
    attempted = len(loop.recs)
    n_served = sum(len(o) for _, o in served)
    del sched, loop
    gc.collect()
    t_ref = time.perf_counter()
    cmp = reference_module(config).compare(config, opt.seed, served,
                                           control=opt.control) \
        if served else {"max_gap": float("inf"),
                        "control_max_gap": float("inf")}
    ref_s = time.perf_counter() - t_ref

    check, correct = judge(check_cfg, cmp["max_gap"], failed, n_served)
    control = None
    if opt.control:
        # the control's first choices in place of the served tokens
        control = judge(check_cfg, cmp["control_max_gap"], failed,
                        n_served)[1]

    if opt.trace:
        metrics = per_layer
    else:
        metrics = {m["name"]: {"value": (setup_s if m["name"] == "setup_s"
                                         else e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    out["_extra"] = {"samples": e2e["samples"], "setup_s": setup_s,
                     "compiles_setup": compiles_setup,
                     "cache_hits_setup": hits_setup,
                     "compiles_window": compiles_window,
                     "reference_s": ref_s, "compare": cmp,
                     "control_correct": control, "memory": mem,
                     "checked_requests": len(served), "e2e": {
                         k: e2e[k] for k in ("output_tok_s", "ttft_p50_ms",
                                             "ttft_p95_ms", "itl_p95_ms")}}
    return out
