"""The traced run: telemetry spans for the whole window, a profiler trace of
its last ``TRACE_SECONDS`` (so that writing the trace out falls after the
window), and the reading that per-layer metrics take their numbers from."""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil
import tempfile
import time
from typing import Optional

from bench.lib import peaks as peaks_mod
from bench.lib import trace as T
from bench.lib.harness import TRACE_SECONDS, log

MATMUL_FAMILIES = ("pallas", "xla", "sharded")
ATTN_FAMILIES = ("attn",)
# A lane's program is the run that best matches its instructions' op_name
# metadata (both lanes are ``jit_step``), accepted only where that share is
# at least MIN_SCORE and the program compiled here holds at least MIN_COVER
# of the distinct instructions that ran in it.  A decode lane that ran in
# the profile and is not accepted is an error: the kernel rooflines would
# otherwise be read against another program, or fall silent.
MIN_SCORE = 0.5
MIN_COVER = 0.99


class Tracer:
    def __init__(self, keep: Optional[str] = None):
        self.keep = keep
        self.dir = keep or tempfile.mkdtemp(prefix="bench_trace_")
        self.stack = contextlib.ExitStack()
        self.rec = None
        self.p0 = self.p1 = None
        self.anchor = None

    def start(self) -> None:
        from repro import telemetry
        self.rec = self.stack.enter_context(telemetry.recording())

    def maybe_profile(self, now: float, close: float) -> None:
        """Start the profiler once the window has TRACE_SECONDS left."""
        if self.p0 is None and now >= close - TRACE_SECONDS:
            self._profile()

    def _profile(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench:anchor"):
            self.anchor = time.perf_counter()
        self.p0 = self.anchor

    def finish(self, loop, sched, cell, mcfg, t_open: float, t_close: float):
        import jax
        if self.p0 is None:
            self._profile()
        jax.profiler.stop_trace()
        self.p1 = t_close
        rec = self.rec
        # telemetry times are microseconds since the recorder's creation
        base = time.perf_counter() - rec.now_us() / 1e6
        spans = [{"name": s["name"], "start": base + s["ts"] / 1e6,
                  "end": base + (s["ts"] + s["dur"]) / 1e6,
                  "args": s.get("args", {})} for s in rec.spans()]
        events = {uid: [(st, base + ts / 1e6, at) for st, ts, at in ev]
                  for uid, ev in rec.request_log().items()}
        self.stack.close()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        prof = jax.profiler.ProfileData.from_file(max(files,
                                                      key=os.path.getmtime))
        flat = T.flatten(prof)
        del prof
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        anchors = [h for h in flat["host"] if h[0] == "bench:anchor"]
        if not anchors:
            raise RuntimeError("the trace holds no bench:anchor annotation")
        offset_ns = anchors[0][1] - self.anchor * 1e9
        lanes, instrs = lane_programs(sched, mcfg) if flat["device"] \
            else ({}, {})
        return Reading(cell=cell, loop=loop, spans=spans,
                       lanes=lanes, instrs=instrs,
                       events=events, trace=flat, offset_ns=offset_ns,
                       p0=self.p0, p1=self.p1, t_open=t_open,
                       t_close=t_close,
                       peaks=_peaks(jax.devices()[0]))


def lane_programs(sched, mcfg) -> dict:
    """Instruction -> name-stack maps of the scheduler's two lanes, and the
    set of all their instructions, read from the compiled programs (lowered
    again from the program's public step builders with the scheduler's own
    state; the compile is a cache hit)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import (make_chunked_prefill_step,
                                    make_paged_decode_step)
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    n, pp, c = sched.n_slots, sched.pages_per_seq, sched.prefill_chunk
    dec = jax.jit(make_paged_decode_step(mcfg, sched.spec)).lower(
        sched.params, sds((n, 1), i32), sched.pools, sched.hot,
        sds((n,), i32), sds((n, pp), i32), sds((n,), jnp.bool_))
    pre = jax.jit(make_chunked_prefill_step(mcfg, sched.spec)).lower(
        sched.params, sds((1, c), i32), sched.pools, sched.hot,
        sds((n, pp), i32), sds((), i32), sds((), i32), sds((), i32))
    texts = {lane: low.compile().as_text()
             for lane, low in (("decode", dec), ("prefill", pre))}
    return ({lane: T.by_base(T.hlo_scopes(t)) for lane, t in texts.items()},
            {lane: T.hlo_instructions(t) for lane, t in texts.items()})


def _peaks(device):
    """The device's peaks; None off the chip (the CPU rehearsal)."""
    try:
        return peaks_mod.for_device(device)
    except KeyError:
        return None


class Reading:
    """What a per-layer metric reads: host spans and request events on the
    perf_counter clock (seconds), the harness's own step records, and the
    flattened device trace on the profiler's clock (ns)."""

    def __init__(self, *, cell, loop, spans, events, trace, offset_ns,
                 p0, p1, t_open, t_close, peaks, lanes=None, instrs=None):
        self.cell, self.spans, self.events = cell, spans, events
        self.lanes = lanes or {}
        self.trace, self.offset_ns = trace, offset_ns
        self.p0, self.p1, self.t_open, self.t_close = p0, p1, t_open, t_close
        self.peaks = peaks
        self.n_slots = int(cell.traffic["n_slots"])
        self.plen = {r.uid: len(r.prompt) for r in loop.recs}
        self.steps = list(loop.steps)
        self.window_s = p1 - p0
        lo, hi = self.ns(p0), self.ns(p1)
        self.busy_s = T.busy_ns(trace, lo, hi) / 1e9
        host = [(s["name"], self.ns(s["start"]), self.ns(s["end"]))
                for s in spans]
        # between two steps the harness's client reads tokens and submits
        host += [("client", self.ns(a[1]), self.ns(b[0]))
                 for a, b in zip(self.steps, self.steps[1:])]
        dev0 = trace["device"][0] if trace["device"] else {"ops": []}
        self.programs = {}
        for lane, names in (self.lanes.items() if trace["device"] else ()):
            prog, score = T.match_program(dev0, names)
            share, missing = T.cover(dev0, prog, (instrs or {}).get(lane, set(
                names)))
            log(f"trace: {lane} lane, {len(names)} instructions with op_name "
                f"compiled; {prog or 'no program'} matches {score:.3f} of its "
                f"ops by op_name, and the compiled program holds {share:.4f} "
                f"of them ({len(missing)} missing: {missing[:8]})")
            self.programs[lane] = prog \
                if score >= MIN_SCORE and share >= MIN_COVER else ""
            if prog:
                self._log_unscoped(dev0, prog, names)
        if self.lanes and trace["device"] and not self.programs.get("decode") \
                and self.decode_calls(p0, p1):
            raise RuntimeError(
                "the decode lane ran in the profile, but no program run "
                f"matches its compiled program (op_name share >= {MIN_SCORE},"
                f" instructions held >= {MIN_COVER}): the kernel rooflines "
                "cannot be read")
        labels = {prog: (lane, self.lanes[lane])
                  for lane, prog in self.programs.items() if prog}
        idle = T.gaps(T.union(T.op_intervals(dev0), lo, hi), lo, hi)
        self.breakdown = {"device_ops": T.top_ops(trace, lo, hi, labels),
                          "idle_gaps": T.label_gaps(idle, host)}
        log(f"trace: {self.window_s:.3f}s profiled, device busy "
            f"{self.busy_s:.3f}s, {len(dev0['ops'])} ops, "
            f"{len(dev0.get('modules', []))} module runs")

    @staticmethod
    def _log_unscoped(dev: dict, prog: str, names: dict) -> None:
        """Log the device time of ``prog``'s ops that carry no op_name (no
        scope can claim them) and the longest of them."""
        agg, total = {}, 0.0
        for instr, _, d, mod in dev["ops"]:
            if mod != prog:
                continue
            total += d
            if not T.stack_of(names, instr):
                agg[instr] = agg.get(instr, 0.0) + d
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:8]
        log(f"trace: {prog}: {len(agg)} instructions without op_name ran "
            f"{sum(agg.values()) / max(total, 1):.4f} of its op time; "
            f"longest {[(k, round(v / 1e6, 3)) for k, v in top]} ms")

    def ns(self, t: float) -> float:
        return t * 1e9 + self.offset_ns

    def window_spans(self, name: str, lo: float = None, hi: float = None):
        lo = self.t_open if lo is None else lo
        hi = self.t_close if hi is None else hi
        return [s for s in self.spans
                if s["name"] == name and s["start"] >= lo and s["end"] <= hi]

    def decode_calls(self, lo: float = None, hi: float = None) -> list:
        """Per decode-lane call of the steps inside [lo, hi]: the step's
        host interval and each active slot's cache length at the call."""
        lo = self.t_open if lo is None else lo
        hi = self.t_close if hi is None else hi
        out = []
        for t0, t1, new in self.steps:
            if t0 < lo or t1 > hi:
                continue
            lens = [self.plen[uid] + k - 1 for uid, k in new if k >= 1]
            if lens:
                out.append((t0, t1, lens))
        return out

    def prefill_chunks(self, lo: float = None, hi: float = None) -> list:
        return [(s["args"]["start"], s["args"]["valid"])
                for s in self.window_spans("sched:prefill_chunk", lo, hi)]

    def lane_device_ns(self, calls: list, families: tuple,
                       lane: str = "decode") -> tuple:
        """Device time of the ops under ``families`` scopes in the ``lane``
        program's run inside each call's step.  Returns the calls that
        had exactly one such run, and their total time (ns)."""
        names = self.lanes.get(lane)
        prog = self.programs.get(lane)
        if not names or not prog:
            return [], 0.0
        dev = self.trace["device"][0]
        ops = dev["ops"]
        starts = [o[1] for o in ops]
        runs = [(s, s + d) for m, s, d in dev["modules"] if m == prog]
        matched, total = [], 0.0
        for call in calls:
            a, b = self.ns(call[0]), self.ns(call[1])
            inside = [r for r in runs if a <= r[1] <= b]
            if len(inside) != 1:
                continue
            s, e = inside[0]
            i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
            matched.append(call)
            total += T.scope_ns(ops[i:j], names, families, s, e)
        return matched, total
