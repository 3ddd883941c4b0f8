"""Reduction of a JAX profiler trace to device busy time, kernel time by
engine scope, and idle gaps labelled by what the host was doing.

A trace is first flattened to plain data (``flatten``), which is also the
format of the recorded fixture under ``bench/tests/data``:

    {"device": [{"name": plane, "ops": [[instr, start_ns, dur_ns, module],
                                        ...],
                 "modules": [[module, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

``instr`` is the HLO instruction an op ran and ``module`` the program run
(``jit_step(<fingerprint>)``) that holds it.  A TPU trace carries no
name-stack on its ops, so an op's engine scope (``pallas:onehot``,
``attn:fused``: the ``jax.named_scope`` of the registry variant) is read
from the ``op_name`` metadata of the compiled program (``hlo_scopes``).
Only host events that the benchmark itself annotated (``bench:`` names)
are kept.
"""
from __future__ import annotations

import bisect
import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
INSTR = re.compile(r"^%?([\w.\-]+)")
HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                      r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
HLO_ANY = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
SCOPE_PART = re.compile(r"(?:^|/)([a-z_]+:[A-Za-z0-9_]+)(?=/|$)")


def flatten(profile, host_prefix: str = "bench:") -> dict:
    """``jax.profiler.ProfileData`` -> plain lists (see module docstring)."""
    device, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            raw, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw += [(ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods += [[ev.name, ev.start_ns, ev.duration_ns]
                             for ev in line.events]
            mods.sort(key=lambda m: m[1])
            starts = [m[1] for m in mods]
            ops = []
            for name, s, d in raw:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][0] if i >= 0 and s < mods[i][1] + mods[i][2] \
                    else ""
                m = INSTR.match(name)
                ops.append([m.group(1) if m else name, s, d, mod])
            ops.sort(key=lambda o: o[1])
            device.append({"name": plane.name, "ops": ops, "modules": mods})
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"device": device, "host": host}


def hlo_instructions(text: str) -> set:
    """Every instruction name of a compiled program's text, with or without
    ``op_name`` metadata (the compiler's own copies and bitcasts have
    none)."""
    return {m.group(1) for m in map(HLO_ANY.match, text.splitlines()) if m}


def hlo_scopes(text: str) -> dict:
    """HLO instruction name -> its ``op_name`` (name-stack), from a compiled
    program's text."""
    out = {}
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


# ------------------------------------------------------------- intervals --

def union(intervals: Iterable, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                if e > lo and s < hi)
    out: list = []
    for s, e in iv:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list, lo: float, hi: float) -> list:
    """Complement of merged ``busy`` within [lo, hi)."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append([cur, s])
        cur = max(cur, e)
    if cur < hi:
        out.append([cur, hi])
    return out


def scopes(text: str) -> list:
    """Engine scopes (``family:variant``) in a name-stack, outermost first."""
    return SCOPE_PART.findall(text)


def op_intervals(dev: dict) -> list:
    return [(s, s + d) for _, s, d, _ in dev["ops"]]


def match_program(dev: dict, names: dict) -> tuple:
    """(program run name, share of its instructions found in ``names``) of
    the run whose ops best match a compiled program's instruction names:
    both lanes are ``jit_step``, told apart by their instructions."""
    seen: dict = {}
    for instr, _, _, mod in dev["ops"]:
        if mod.startswith("jit_"):
            seen.setdefault(mod, set()).add(instr)
    best, score = "", 0.0
    for mod, instrs in seen.items():
        f = len(instrs & names.keys()) / len(instrs)
        if f > score:
            best, score = mod, f
    return best, score


def cover(dev: dict, program: str, instrs: set) -> tuple:
    """Share of the distinct instructions that ran in ``program``'s runs
    which a compiled program holds (1.0 when it is the program that ran),
    and the ones it does not hold."""
    ran = {instr for instr, _, _, mod in dev["ops"] if mod == program}
    missing = sorted(ran - instrs)
    return 1.0 - len(missing) / max(len(ran), 1), missing


def by_base(names: dict) -> dict:
    """Instruction -> name-stack, also under each instruction's base name
    (``strum_onehot`` for ``strum_onehot.64``) where every instruction of
    that base shares one stack of engine scopes: kernels keep their name
    when a recompile renumbers them."""
    base: dict = {}
    for instr, stack in names.items():
        base.setdefault(instr.rsplit(".", 1)[0], set()).add(
            tuple(scopes(stack)))
    out = {b: "/".join(next(iter(sc))) for b, sc in base.items()
           if len(sc) == 1 and next(iter(sc))}
    out.update(names)
    return out


def stack_of(names: dict, instr: str) -> str:
    return names.get(instr) or names.get(instr.rsplit(".", 1)[0], "")


def busy_ns(trace: dict, lo: float, hi: float) -> float:
    """Mean over the device planes of the union of op intervals."""
    planes = trace["device"]
    if not planes:
        return 0.0
    return sum(covered(union(op_intervals(p), lo, hi)) for p in planes) \
        / len(planes)


def in_families(name_stack: str, families: tuple) -> bool:
    """Does the name-stack pass through a scope of one of ``families``
    (``pallas:onehot`` is of ``pallas``)?  Kernels nest their own scopes
    (``strum:*``) inside the engine's variant scope."""
    return any(sc.split(":")[0] in families for sc in scopes(name_stack))


def scope_ns(ops: Iterable, names: dict, families: tuple, lo: float,
             hi: float) -> float:
    """Device time of ops (of one program, ``names`` its instruction ->
    name-stack map) under a scope of ``families``."""
    t = 0.0
    for instr, s, d, _ in ops:
        if lo <= s < hi and in_families(stack_of(names, instr), families):
            t += d
    return t


def top_ops(trace: dict, lo: float, hi: float, labels: dict,
            n: int = 10) -> list:
    """Device time by op: ``<program>/<innermost scope>/<instruction>``,
    where ``labels`` maps a program run name to (label, names)."""
    agg: dict = {}
    planes = trace["device"]
    for p in planes:
        ops = p["ops"]
        for i, (instr, s, d, mod) in enumerate(ops):
            if s < lo or s >= hi:
                continue
            if i + 1 < len(ops) and ops[i + 1][1] < s + d:
                continue        # a loop or call that holds the next ops
            label, names = labels.get(mod, (mod.split("(")[0], {}))
            sc = scopes(stack_of(names, instr))
            key = "/".join([label] + sc[-1:] + [instr])
            agg[key] = agg.get(key, 0.0) + d
    k = max(len(planes), 1)
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in rows]


def label_gaps(idle: list, spans: list, n: int = 10) -> list:
    """Longest idle gaps, each named by the innermost host span that covers
    its midpoint. ``spans``: [(name, start_ns, end_ns), ...]."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "host:untraced"
        out.append([name, (e - s) / 1e9])
    return out
