"""Static quantization-error analysis: an abstract interpreter over jaxprs.

Propagates quantization-error intervals and second moments from every StruM
decode site (the PACKED payload leaves ``dataflow.py``'s taint analysis
tags) through the traced program — matmuls, softmax, rms-norm, scans —
to a statically derived per-leaf and end-to-end output-error bound for any
``(params, schedule)`` pair.

Abstract domain (:class:`ErrVal`), one value per traced variable:

* ``[lo, hi]`` — a *joint* interval: it bounds the value in the fp program
  AND in every (partially-)quantized variant.  Leaf intervals are hulls
  over ``W`` and ``W_hat``; all transfer rules are value-agnostic, so the
  property is preserved by construction.
* ``err[tag]`` — sound per-payload-leaf error: a bound on how much the
  value moves when leaf ``tag`` alone is swapped from ``W`` to ``W_hat``.
  By a telescoping/hybrid argument ``sum_t err[tag]`` bounds the fully
  quantized program, and because the interval is joint, every ``err[tag]``
  can be capped at the interval width — this is what keeps the bound
  finite through softmax and rms-norm.
* ``ms`` / ``err2[tag]`` — *estimate* channels (mean square of the value,
  mean-square error per leaf) used by the activation-aware autotune proxy
  (:func:`output_gains`); no soundness claim.
* ``const`` — exact concrete value, tracked whenever an equation's inputs
  are all exact (errors empty) and cheap to evaluate: this resolves iota /
  rope tables / masks / ``cond`` predicates exactly, which the scan
  unroller uses to walk only the taken branch.

Packed payload leaves (``mask``/``hi``/``lo``/``scale``) are carried as
opaque *payload-pure* markers; the decode arithmetic (shifts, xor, cumsum)
is never numerically interpreted.  At the first equation that mixes a
float payload-pure value with ordinary program values (the matmul against
activations), the payload is materialized to precomputed
:class:`LeafStats` of its dequantized leaf — robust to any decode
lowering.

Four refinements keep the interval domain tight where naive interval
arithmetic explodes:

* **dominated-sub** — ``sub(a, group_max(a))``-shaped values are clamped
  to ``<= 0`` (so ``exp`` lands in ``[0, 1]``);
* **softmax-denominator** — ``reduce_sum(exp(x - group_max(x)))`` is
  ``>= 1`` (the argmax contributes ``exp(0)``);
* **flash-normalizer** — the online-softmax scan of
  ``models.attention._chunked_causal`` is structurally verified (carry
  algebra ``l' = l*corr + sum(p)``, ``m' = max(m, max(sc))``, exact cond
  predicates) and proves ``l_final >= 1``, so the ``acc / max(l, eps)``
  normalization divides by ``[1, hi]`` instead of ``[eps, hi]``;
* **rms-norm** — ``x * rsqrt(mean(x^2) + eps)`` is bounded by
  ``sqrt(n)`` element-wise for any ``x``.

All refinements are tightness-only: if a matcher misses (different trace
idiom), bounds stay sound, just wider.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import numpy as np

from repro.analysis.report import Report

__all__ = ["LeafStats", "ErrVal", "NumericsResult", "leaf_stats_from_plan",
           "analyze", "output_gains", "measured_error", "check_error_budget",
           "per_tensor_bound", "PAYLOAD_KEYS", "SCALE_KEY"]

PAYLOAD_KEYS = ("mask", "hi", "lo")
SCALE_KEY = "scale"

INF = float("inf")
#: largest array the interpreter will materialize for exact const tracking
_CONST_SIZE_LIMIT = 1 << 17
#: scans longer than this are not unrolled (outputs go to TOP)
_SCAN_UNROLL_LIMIT = 512
_EXP_CLAMP = 709.0

_PASS_THROUGH = frozenset({
    "broadcast_in_dim", "reshape", "convert_element_type", "squeeze",
    "transpose", "copy", "stop_gradient", "expand_dims",
})


# ---------------------------------------------------------------------------
# leaf statistics


@dataclasses.dataclass(frozen=True)
class LeafStats:
    """Precomputed numerics of one quantized leaf: joint hull of ``W`` and
    ``W_hat``, max-abs / mean-square quantization error, signal power."""

    lo: float
    hi: float
    err: float
    err2: float
    ms: float


def leaf_stats_from_plan(plan, ref_params) -> dict:
    """Per-entry :class:`LeafStats` for an :class:`ExecutionPlan`, against
    the original float leaves in ``ref_params``.  The hull includes 0 so a
    padded-K decode (zero-filled tail) stays inside it."""
    from repro.core.apply import _named_leaves
    named = dict(_named_leaves(ref_params))
    out = {}
    for name, entry in plan.entries.items():
        w = np.asarray(named[name], dtype=np.float64)
        wq = np.asarray(entry.dequantized(), dtype=np.float64)
        d = wq - w
        out[name] = LeafStats(
            lo=float(min(w.min(), wq.min(), 0.0)),
            hi=float(max(w.max(), wq.max(), 0.0)),
            err=float(np.max(np.abs(d))),
            err2=float(np.mean(d * d)),
            ms=float(np.mean(w * w)))
    return out


def per_tensor_bound(entry, ref_leaf) -> float:
    """Unit-input local output-error bound for one plan entry:
    ``max_n sum_k |W_hat - W|[k, n]`` — the worst-case error of
    ``x @ W_hat`` vs ``x @ W`` over ``|x|_inf <= 1``."""
    w = np.asarray(ref_leaf, dtype=np.float64)
    wq = np.asarray(entry.dequantized(), dtype=np.float64)
    d = np.abs(wq - w)
    k_axis = max(0, d.ndim - 2)      # leaf layout is (..., K, N)
    return float(d.sum(axis=k_axis).max())


# ---------------------------------------------------------------------------
# abstract values


def _xmul(a: float, b: float) -> float:
    """inf-safe product: 0 * inf -> 0 (a zero interval/error annihilates
    an unbounded factor because actual values are finite)."""
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


def _bounds_mul(alo, ahi, blo, bhi):
    ps = (_xmul(alo, blo), _xmul(alo, bhi), _xmul(ahi, blo), _xmul(ahi, bhi))
    return min(ps), max(ps)


def _esum(*dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for t, v in d.items():
            out[t] = out.get(t, 0.0) + v
    return out


def _emax(*dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for t, v in d.items():
            out[t] = max(out.get(t, 0.0), v)
    return out


def _escale(d: dict, k: float) -> dict:
    return {t: _xmul(v, k) for t, v in d.items()}


@dataclasses.dataclass
class ErrVal:
    """Abstract value: joint interval, per-tag sound error, estimate
    channels, and optional payload marker / exact const."""

    lo: float = -INF
    hi: float = INF
    err: dict = dataclasses.field(default_factory=dict)
    ms: float = 0.0
    err2: dict = dataclasses.field(default_factory=dict)
    payload: Optional[frozenset] = None
    const: Optional[np.ndarray] = None

    @property
    def width(self) -> float:
        return self.hi - self.lo

    @property
    def mag(self) -> float:
        return max(abs(self.lo), abs(self.hi))

    def total_err(self) -> float:
        return min(sum(self.err.values()), self.width) if self.err else 0.0

    def exact(self) -> bool:
        return not any(v > 0.0 for v in self.err.values())


def _cap(ev: ErrVal) -> ErrVal:
    """Clamp each per-tag error at the joint interval width (sound: both
    the fp and the variant value live inside ``[lo, hi]``).  The ``err2``
    estimate channel is capped at width^2 — a saturation model: a
    deviation's power cannot exceed the square of the range it lives in.
    Consequence: ``err2`` propagation is linear only while seeds stay
    small against the intervals they flow through (the regime real
    quantization noise occupies); :func:`output_gains`'s unit seeds
    deliberately saturate at the leaf, yielding range-aware gains."""
    w = ev.hi - ev.lo
    if math.isfinite(w):
        ev.err = {t: min(v, w) for t, v in ev.err.items() if v > 0.0}
        w2 = w * w
        ev.err2 = {t: min(v, w2) for t, v in ev.err2.items() if v > 0.0}
    return ev


def _from_array(x) -> ErrVal:
    a = np.asarray(x)
    if a.size == 0:
        return ErrVal(lo=0.0, hi=0.0, ms=0.0, const=a)
    if a.dtype == np.bool_:
        a = a.astype(np.int32)
    af = a.astype(np.float64)
    return ErrVal(lo=float(af.min()), hi=float(af.max()),
                  ms=float(np.mean(af * af)),
                  const=a if a.size <= _CONST_SIZE_LIMIT else None)


def _from_stats(s: LeafStats, tag: str) -> ErrVal:
    return ErrVal(lo=s.lo, hi=s.hi, err={tag: s.err} if s.err else {},
                  ms=s.ms, err2={tag: s.err2} if s.err2 else {})


def _top(tags) -> ErrVal:
    tags = set(tags)
    return ErrVal(err={t: INF for t in tags}, err2={t: INF for t in tags})


def _join_vals(vals) -> ErrVal:
    vals = [v for v in vals if v is not None]
    if not vals:
        return ErrVal(lo=0.0, hi=0.0)
    if all(v.payload is not None for v in vals):
        return ErrVal(payload=frozenset().union(*(v.payload for v in vals)))
    consts = [v.const for v in vals]
    const = None
    if all(c is not None for c in consts) and all(v.exact() for v in vals):
        try:
            stacked = np.stack(consts)
            if stacked.size <= _CONST_SIZE_LIMIT:
                const = stacked
        except ValueError:
            const = None
    return _cap(ErrVal(
        lo=min(v.lo for v in vals), hi=max(v.hi for v in vals),
        err=_emax(*(v.err for v in vals)),
        ms=sum(v.ms for v in vals) / len(vals),
        err2=_emax(*(v.err2 for v in vals)),
        const=const))


# ---------------------------------------------------------------------------
# context and generic walk machinery


@dataclasses.dataclass
class _Ctx:
    stats: dict
    report: Report
    location: str
    unroll_limit: int
    seeds: dict = dataclasses.field(default_factory=dict)
    env: dict = dataclasses.field(default_factory=dict)
    defs: dict = dataclasses.field(default_factory=dict)
    alias: dict = dataclasses.field(default_factory=dict)
    unsupported: set = dataclasses.field(default_factory=set)
    flash_cache: dict = dataclasses.field(default_factory=dict)

    def note_unsupported(self, prim: str, why: str) -> None:
        if prim not in self.unsupported:
            self.unsupported.add(prim)
            self.report.add("info", "numerics/unsupported-op",
                            f"{self.location}: {prim}", why)


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")


def _read(ctx: _Ctx, atom) -> ErrVal:
    if _is_literal(atom):
        return _from_array(atom.val)
    ev = ctx.env.get(atom)
    if ev is None:
        return ErrVal()          # unseeded input: unknown but error-free
    return ev


def _resolve(ctx: _Ctx, atom):
    """Follow alias links (jit inlining, cond branch operands)."""
    seen = 0
    while not _is_literal(atom) and atom in ctx.alias and seen < 64:
        atom = ctx.alias[atom]
        seen += 1
    return atom


def _strip(ctx: _Ctx, atom):
    """Resolve aliases and strip shape-only pass-through eqns; returns the
    defining core atom."""
    for _ in range(128):
        atom = _resolve(ctx, atom)
        if _is_literal(atom):
            return atom
        eqn = ctx.defs.get(atom)
        if eqn is None or eqn.primitive.name not in _PASS_THROUGH:
            return atom
        atom = eqn.invars[0]
    return atom


def _def_of(ctx: _Ctx, atom, prim: str):
    """The defining eqn of ``atom`` (after stripping) if its primitive is
    ``prim``, else None."""
    core = _strip(ctx, atom)
    if _is_literal(core):
        return None
    eqn = ctx.defs.get(core)
    if eqn is not None and eqn.primitive.name == prim:
        return eqn
    return None


# --- group-max dominance (refinements R1/R2) -------------------------------


def _chain_dim_map(ctx: _Ctx, atom):
    """Walk ``atom`` backward through broadcast/reshape-style eqns.
    Returns ``(core_atom, dim_map)`` where ``dim_map`` maps each dim of
    ``core`` to the dim of the original ``atom`` it is faithfully copied
    to (broadcast dims are dropped)."""
    atom = _resolve(ctx, atom)
    if _is_literal(atom):
        return atom, {}
    rank = len(atom.aval.shape)
    m = {d: d for d in range(rank)}
    for _ in range(64):
        atom = _resolve(ctx, atom)
        if _is_literal(atom):
            return atom, m
        eqn = ctx.defs.get(atom)
        if eqn is None:
            return atom, m
        p = eqn.primitive.name
        if p == "broadcast_in_dim":
            bd = eqn.params["broadcast_dimensions"]
            inp = eqn.invars[0]
            if _is_literal(inp):
                return inp, {}
            new_m = {}
            for j, outd in enumerate(bd):
                if (outd in m and inp.aval.shape[j]
                        == eqn.outvars[0].aval.shape[outd]):
                    new_m[j] = m[outd]
            m, atom = new_m, inp
        elif p in ("convert_element_type", "copy", "stop_gradient"):
            atom = eqn.invars[0]
        elif p in ("reshape", "squeeze", "expand_dims"):
            inp = eqn.invars[0]
            if _is_literal(inp):
                return inp, {}
            out_shape = eqn.outvars[0].aval.shape
            in_shape = inp.aval.shape
            nz_out = [d for d, s in enumerate(out_shape) if s != 1]
            nz_in = [d for d, s in enumerate(in_shape) if s != 1]
            if ([out_shape[d] for d in nz_out]
                    != [in_shape[d] for d in nz_in]):
                return atom, m    # a genuine reshape: stop here
            new_m = {}
            for di, do in zip(nz_in, nz_out):
                if do in m:
                    new_m[di] = m[do]
            m, atom = new_m, inp
        else:
            return atom, m
    return atom, m


def _group_covers(a_var, dim_map, axes) -> bool:
    """True when a reduce over ``axes`` of ``a``, re-broadcast along
    ``dim_map``, puts each element of ``a`` inside its own group."""
    a_shape = a_var.aval.shape
    red_rank = len(a_shape) - len(axes)
    kept = [d for d in range(len(a_shape)) if d not in axes]
    if red_rank < 0:
        return False
    for j, d in enumerate(kept):
        if a_shape[d] == 1:
            continue
        if dim_map.get(j) is None:
            return False
        # dim_map maps reduce-output dim j to a dim of the broadcast
        # result; with rank-aligned elementwise ops that dim must be d.
        if dim_map[j] != d:
            return False
    return True


def _dominating_group_max(ctx: _Ctx, b_atom, a_atom,
                          require_plain: bool = False):
    """Check ``b >= a`` element-wise because ``b`` is (a broadcast of)
    ``max(other, reduce_max(a, axes))``, ``reduce_max(a, axes)`` itself, or
    ``max(..., a, ...)``.  Returns the reduce axes tuple (or ``()`` for the
    direct-operand case), or ``None`` if no proof."""
    a_res = _resolve(ctx, a_atom)
    core, dim_map = _chain_dim_map(ctx, b_atom)
    if _is_literal(core):
        return None
    eqn = ctx.defs.get(core)
    if eqn is None:
        return None
    candidates = []
    if eqn.primitive.name == "reduce_max":
        candidates.append((eqn, dim_map))
    elif eqn.primitive.name == "max" and not require_plain:
        for op in eqn.invars:
            if _resolve(ctx, op) is a_res and not dim_map_broadcasts(
                    core, dim_map):
                return ()
            rm = _def_of(ctx, op, "reduce_max")
            if rm is not None:
                candidates.append((rm, dim_map))
    for rm, dm in candidates:
        if _resolve(ctx, rm.invars[0]) is not a_res:
            continue
        axes = tuple(rm.params["axes"])
        if _group_covers(a_res, dm, axes):
            return axes
    return None


def dim_map_broadcasts(core_var, dim_map) -> bool:
    """True if the chain from ``core_var`` broadcasts any non-unit dim."""
    shape = core_var.aval.shape
    return any(s != 1 and dim_map.get(d) != d for d, s in enumerate(shape))


# --- rms-norm refinement (R4) ----------------------------------------------


def _scalar_const(ctx: _Ctx, atom) -> Optional[float]:
    ev = _read(ctx, atom)
    if ev.const is not None and ev.exact() and np.asarray(ev.const).size == 1:
        return float(np.asarray(ev.const).reshape(()))
    return None


def _match_rms(ctx: _Ctx, x_atom, r_atom) -> Optional[float]:
    """Match ``r = rsqrt(mean_G(x^2)/n + eps)`` (broadcast back over the
    reduced group); returns ``sqrt(n)`` — the element-wise bound of
    ``x * r`` — or None."""
    rs = _def_of(ctx, r_atom, "rsqrt")
    if rs is None:
        return None
    add = _def_of(ctx, rs.invars[0], "add")
    if add is None:
        return None
    eps = None
    mean_atom = None
    for u, v in ((add.invars[0], add.invars[1]),
                 (add.invars[1], add.invars[0])):
        c = _scalar_const(ctx, v)
        if c is not None and c > 0.0:
            eps, mean_atom = c, u
            break
    if eps is None:
        return None
    n = None
    core = None
    dv = _def_of(ctx, mean_atom, "div")
    if dv is not None:
        c = _scalar_const(ctx, dv.invars[1])
        if c is not None and c > 0.0:
            n, core = c, dv.invars[0]
    if n is None:
        ml = _def_of(ctx, mean_atom, "mul")
        if ml is not None:
            for u, v in ((ml.invars[0], ml.invars[1]),
                         (ml.invars[1], ml.invars[0])):
                c = _scalar_const(ctx, v)
                if c is not None and c > 0.0:
                    n, core = 1.0 / c, u
                    break
    if n is None:
        return None
    _, dim_map = _chain_dim_map(ctx, core)
    rsum = _def_of(ctx, core, "reduce_sum")
    if rsum is None:
        return None
    axes = tuple(rsum.params["axes"])
    sq_atom = rsum.invars[0]
    sq = _def_of(ctx, sq_atom, "square")
    x2 = None
    if sq is not None:
        x2 = sq.invars[0]
    else:
        ip = _def_of(ctx, sq_atom, "integer_pow")
        if ip is not None and ip.params.get("y") == 2:
            x2 = ip.invars[0]
        else:
            ml = _def_of(ctx, sq_atom, "mul")
            if ml is not None and _resolve(ctx, ml.invars[0]) is _resolve(
                    ctx, ml.invars[1]):
                x2 = ml.invars[0]
    if x2 is None or _resolve(ctx, x2) is not _resolve(ctx, x_atom):
        return None
    x_res = _resolve(ctx, x_atom)
    if _is_literal(x_res) or not _group_covers(x_res, dim_map, axes):
        return None
    return math.sqrt(n)


# ---------------------------------------------------------------------------
# flash-normalizer (online softmax) scan verification (R3)


@dataclasses.dataclass
class _FlashMatch:
    cond_eqn: object
    update_branch: int
    x_var: object       # score var inside the update branch jaxpr
    l_pos: int          # carry position of the softmax denominator
    m_pos: int          # carry position of the running max


def _branch_defs(jaxpr) -> dict:
    return {ov: e for e in jaxpr.eqns for ov in e.outvars}


def _local_strip(defs: dict, alias: dict, atom):
    for _ in range(64):
        while not _is_literal(atom) and atom in alias:
            atom = alias[atom]
        if _is_literal(atom):
            return atom
        eqn = defs.get(atom)
        if eqn is None or eqn.primitive.name not in _PASS_THROUGH:
            return atom
        atom = eqn.invars[0]
    return atom


def _match_flash_scan(scan_eqn) -> Optional[_FlashMatch]:
    """Structurally verify the online-softmax normalizer carry of a scan
    whose body dispatches through a 2-branch ``cond`` (one identity
    branch, one update branch computing ``l' = l*corr + sum(exp(x - m'))``
    with ``m' = max(m, reduce_max(x))`` and ``corr = exp(m - m')``).

    The accompanying induction (see module docstring) proves
    ``l_final >= 1`` once at least one update ran and the ``m`` init is
    ``<=`` every score's joint lower bound — both checked dynamically by
    the unroller."""
    p = scan_eqn.params
    nc, ncar = p["num_consts"], p["num_carry"]
    if ncar < 2:
        return None
    body = p["jaxpr"].jaxpr
    carry_vars = list(body.invars[nc:nc + ncar])
    bdefs = _branch_defs(body)

    for jl in range(ncar):
        lov = body.outvars[jl]
        if _is_literal(lov):
            continue
        cond = bdefs.get(lov)
        if cond is None or cond.primitive.name != "cond":
            continue
        branches = cond.params["branches"]
        if len(branches) != 2:
            continue
        pos_l = list(cond.outvars).index(lov)

        def to_body_atom(br_jaxpr, atom):
            """Map a branch invar back to the cond operand in the body."""
            atom = _local_strip(_branch_defs(br_jaxpr), {}, atom)
            if _is_literal(atom):
                return atom
            try:
                k = list(br_jaxpr.invars).index(atom)
            except ValueError:
                return None
            return cond.invars[1 + k]

        for upb in (0, 1):
            idb = 1 - upb
            m = _match_flash_update(cond, branches[upb].jaxpr,
                                    branches[idb].jaxpr, pos_l,
                                    carry_vars, jl, to_body_atom, body)
            if m is not None:
                return _FlashMatch(cond_eqn=cond, update_branch=upb,
                                   x_var=m[0], l_pos=jl, m_pos=m[1])
    return None


def _match_flash_update(cond, up, idn, pos_l, carry_vars, jl,
                        to_body_atom, body) -> Optional[tuple]:
    """Match the update/identity branch pair; returns ``(x_var, m_pos)``
    or None."""
    updefs = _branch_defs(up)

    def ustrip(atom):
        return _local_strip(updefs, {}, atom)

    def carry_index(br, atom):
        r = to_body_atom(br, atom)
        if r is None or _is_literal(r):
            return None
        try:
            return carry_vars.index(r)
        except ValueError:
            return None

    # identity branch must return the l carry unchanged
    if carry_index(idn, idn.outvars[pos_l]) != jl:
        return None

    add = updefs.get(ustrip(up.outvars[pos_l]))
    if add is None or add.primitive.name != "add":
        return None
    for rs_atom, mul_atom in ((add.invars[0], add.invars[1]),
                              (add.invars[1], add.invars[0])):
        rsum = updefs.get(ustrip(rs_atom))
        mul = updefs.get(ustrip(mul_atom))
        if rsum is None or rsum.primitive.name != "reduce_sum":
            continue
        if mul is None or mul.primitive.name != "mul":
            continue
        axes = tuple(rsum.params["axes"])
        # l_in * corr with corr = exp(sub(m_in, m_new))
        for li_atom, corr_atom in ((mul.invars[0], mul.invars[1]),
                                   (mul.invars[1], mul.invars[0])):
            if carry_index(up, li_atom) != jl:
                continue
            cexp = updefs.get(ustrip(corr_atom))
            if cexp is None or cexp.primitive.name != "exp":
                continue
            csub = updefs.get(ustrip(cexp.invars[0]))
            if csub is None or csub.primitive.name != "sub":
                continue
            qm = carry_index(up, csub.invars[0])
            if qm is None or qm == jl:
                continue
            m_new = ustrip(csub.invars[1])
            # p = exp(sub(x, broadcast(m_new)))
            pexp = updefs.get(ustrip(rsum.invars[0]))
            if pexp is None or pexp.primitive.name != "exp":
                continue
            psub = updefs.get(ustrip(pexp.invars[0]))
            if psub is None or psub.primitive.name != "sub":
                continue
            x_var = psub.invars[0]
            if _is_literal(x_var):
                continue
            bcore, dim_map = _chain_dim_map(
                _Ctx(stats={}, report=Report(), location="",
                     unroll_limit=0, defs=updefs), psub.invars[1])
            if bcore is not m_new:
                continue
            # m_new = max(m_in, reduce_max(x, axes))
            mx = updefs.get(m_new)
            if mx is None or mx.primitive.name != "max":
                continue
            ok = False
            for u_at, v_at in ((mx.invars[0], mx.invars[1]),
                               (mx.invars[1], mx.invars[0])):
                if carry_index(up, u_at) != qm:
                    continue
                rmax = updefs.get(ustrip(v_at))
                if (rmax is not None
                        and rmax.primitive.name == "reduce_max"
                        and ustrip(rmax.invars[0]) is ustrip(x_var)
                        and tuple(rmax.params["axes"]) == axes):
                    ok = True
                    break
            if not ok:
                continue
            x_res = ustrip(x_var)
            if _is_literal(x_res) or not _group_covers(
                    x_res, dim_map, axes):
                continue
            # m carry-out: update branch emits m_new, identity returns m
            try:
                pos_m = list(cond.outvars).index(body.outvars[qm])
            except ValueError:
                continue
            if ustrip(up.outvars[pos_m]) is not m_new:
                continue
            if carry_index(idn, idn.outvars[pos_m]) != qm:
                continue
            return (x_var, qm)
    return None


# ---------------------------------------------------------------------------
# transfer rules


def _unary_lipschitz(ev: ErrVal, lo: float, hi: float, lip: float,
                     ms: Optional[float] = None) -> ErrVal:
    if ms is None:
        ms = ((abs(lo) + abs(hi)) / 2.0) ** 2 if math.isfinite(
            lo) and math.isfinite(hi) else INF
    return ErrVal(lo=lo, hi=hi, err=_escale(ev.err, lip), ms=ms,
                  err2=_escale(ev.err2, lip * lip))


def _exp_hi(x: float) -> float:
    return INF if x >= _EXP_CLAMP else math.exp(x)


def _rule_add(ctx, eqn, ins):
    a, b = ins
    if eqn.primitive.name == "sub":
        lo, hi = a.lo - b.hi, a.hi - b.lo
        dom = _dominating_group_max(ctx, eqn.invars[1], eqn.invars[0])
        if dom is not None:
            hi = min(hi, 0.0)
    else:
        lo, hi = a.lo + b.lo, a.hi + b.hi
    return ErrVal(lo=lo, hi=hi, err=_esum(a.err, b.err), ms=a.ms + b.ms,
                  err2=_esum(a.err2, b.err2))


def _rule_mul(ctx, eqn, ins):
    a, b = ins
    lo, hi = _bounds_mul(a.lo, a.hi, b.lo, b.hi)
    err = _esum(_escale(b.err, a.mag), _escale(a.err, b.mag))
    err2 = _esum(_escale(b.err2, a.ms), _escale(a.err2, b.ms))
    out = ErrVal(lo=lo, hi=hi, err=err, ms=a.ms * b.ms, err2=err2)
    for x_atom, r_atom, x_ev in ((eqn.invars[0], eqn.invars[1], a),
                                 (eqn.invars[1], eqn.invars[0], b)):
        bound = _match_rms(ctx, x_atom, r_atom)
        if bound is not None:
            out.lo, out.hi = max(out.lo, -bound), min(out.hi, bound)
            out.ms = min(out.ms, 1.0) if out.ms else 1.0
            denom = max(x_ev.ms, 1e-12)
            out.err2 = {t: min(v, x_ev.err2.get(t, INF) / denom)
                        for t, v in out.err2.items()}
            break
    return out


def _rule_div(ctx, eqn, ins):
    a, b = ins
    if b.lo <= 0.0 <= b.hi:
        ctx.report.add("info", "numerics/unbounded",
                       f"{ctx.location}: div",
                       "denominator interval spans zero; the static bound "
                       "is unbounded from this point on")
        return _top(set(a.err) | set(b.err))
    bmin = min(abs(b.lo), abs(b.hi))
    qs = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
    err = _esum(_escale(a.err, 1.0 / bmin),
                _escale(b.err, a.mag / (bmin * bmin)))
    bms = max(b.ms, 1e-30)
    err2 = _esum(_escale(a.err2, 1.0 / bms),
                 _escale(b.err2, a.ms / (bms * bms)))
    return ErrVal(lo=min(qs), hi=max(qs), err=err, ms=a.ms / bms, err2=err2)


def _contraction_size(eqn) -> int:
    prim = eqn.primitive.name
    if prim == "dot_general":
        (lc, _), _ = eqn.params["dimension_numbers"]
        shape = eqn.invars[0].aval.shape
        return int(np.prod([shape[d] for d in lc])) if lc else 1
    # conv_general_dilated: everything but the output-feature dim of rhs
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    out_dim = dn.rhs_spec[0]
    k = int(np.prod(rhs)) // max(1, rhs[out_dim])
    return max(1, k)


def _rule_dot(ctx, eqn, ins):
    a, b = ins
    k = _contraction_size(eqn)
    plo, phi = _bounds_mul(a.lo, a.hi, b.lo, b.hi)
    err = _escale(_esum(_escale(b.err, a.mag), _escale(a.err, b.mag)),
                  float(k))
    err2 = _escale(_esum(_escale(b.err2, a.ms), _escale(a.err2, b.ms)),
                   float(k))
    return ErrVal(lo=_xmul(k, plo), hi=_xmul(k, phi), err=err,
                  ms=_xmul(k, a.ms * b.ms), err2=err2)


def _reduced_count(eqn) -> int:
    shape = eqn.invars[0].aval.shape
    axes = eqn.params["axes"]
    return int(np.prod([shape[d] for d in axes])) if axes else 1


def _rule_reduce_sum(ctx, eqn, ins):
    (a,) = ins
    n = _reduced_count(eqn)
    out = ErrVal(lo=_xmul(n, a.lo), hi=_xmul(n, a.hi),
                 err=_escale(a.err, float(n)), ms=_xmul(n, a.ms),
                 err2=_escale(a.err2, float(n)))
    # softmax denominator: sum(exp(x - group_max(x))) >= exp(0) = 1
    ex = _def_of(ctx, eqn.invars[0], "exp")
    if ex is not None:
        sb = _def_of(ctx, ex.invars[0], "sub")
        if sb is not None:
            axes = _dominating_group_max(ctx, sb.invars[1], sb.invars[0],
                                         require_plain=True)
            if axes is not None and tuple(axes) == tuple(
                    eqn.params["axes"]):
                out.lo = max(out.lo, 1.0)
    return out


def _rule_reduce_minmax(ctx, eqn, ins):
    (a,) = ins
    return ErrVal(lo=a.lo, hi=a.hi, err=dict(a.err), ms=a.ms,
                  err2=dict(a.err2))


def _rule_cumsum(ctx, eqn, ins):
    (a,) = ins
    n = eqn.invars[0].aval.shape[eqn.params.get("axis", 0)]
    return ErrVal(lo=min(a.lo, _xmul(n, a.lo)), hi=max(a.hi, _xmul(n, a.hi)),
                  err=_escale(a.err, float(n)), ms=_xmul(n, a.ms),
                  err2=_escale(a.err2, float(n)))


def _rule_exp(ctx, eqn, ins):
    (a,) = ins
    hi = _exp_hi(a.hi)
    lo = 0.0 if a.lo == -INF else _exp_hi(a.lo)
    return _unary_lipschitz(a, lo, hi, hi)


def _rule_elementwise_minmax(ctx, eqn, ins):
    a, b = ins
    if eqn.primitive.name == "max":
        lo, hi = max(a.lo, b.lo), max(a.hi, b.hi)
    else:
        lo, hi = min(a.lo, b.lo), min(a.hi, b.hi)
    return ErrVal(lo=lo, hi=hi, err=_emax(a.err, b.err),
                  ms=max(a.ms, b.ms), err2=_emax(a.err2, b.err2))


def _rule_select(ctx, eqn, ins):
    pred, cases = ins[0], ins[1:]
    if pred.const is not None and pred.exact():
        vals = np.unique(np.asarray(pred.const).astype(np.int64))
        if len(vals) == 1 and 0 <= int(vals[0]) < len(cases):
            c = cases[int(vals[0])]
            return ErrVal(lo=c.lo, hi=c.hi, err=dict(c.err), ms=c.ms,
                          err2=dict(c.err2), const=c.const)
        picked = [cases[int(v)] for v in vals if 0 <= int(v) < len(cases)]
        out = _join_vals(picked or list(cases))
        out.const = None
        return out
    out = _join_vals(list(cases))
    out.const = None
    if not pred.exact():
        w = out.width
        for t, v in pred.err.items():
            if v > 0.0:
                out.err[t] = out.err.get(t, 0.0) + w
                out.err2[t] = out.err2.get(t, 0.0) + (
                    w * w if math.isfinite(w) else INF)
    return out


def _rule_compare(ctx, eqn, ins):
    err = {}
    err2 = {}
    for ev in ins:
        for t, v in ev.err.items():
            if v > 0.0:
                err[t] = 1.0
                err2[t] = 1.0
    return ErrVal(lo=0.0, hi=1.0, err=err, ms=0.5, err2=err2)


def _rule_pass(ctx, eqn, ins):
    a = ins[0]
    return ErrVal(lo=a.lo, hi=a.hi, err=dict(a.err), ms=a.ms,
                  err2=dict(a.err2))


def _rule_gather(ctx, eqn, ins):
    a = ins[0]
    # fill-mode gathers may introduce zeros: widen the hull to include 0
    return ErrVal(lo=min(a.lo, 0.0), hi=max(a.hi, 0.0), err=dict(a.err),
                  ms=a.ms, err2=dict(a.err2))


def _rule_join(ctx, eqn, ins):
    out = _join_vals([ev for ev in ins
                      if getattr(ev, "payload", None) is None])
    out.const = None
    return out


def _rule_pad(ctx, eqn, ins):
    return _join_vals(ins[:2])


def _rule_iota(ctx, eqn, ins):
    n = eqn.outvars[0].aval.shape[eqn.params["dimension"]]
    return ErrVal(lo=0.0, hi=float(max(0, n - 1)), ms=(n - 1) ** 2 / 3.0)


def _rule_square(ctx, eqn, ins):
    (a,) = ins
    cands = [a.lo * a.lo, a.hi * a.hi]
    lo = 0.0 if a.lo <= 0.0 <= a.hi else min(cands)
    lip = 2.0 * a.mag
    return _unary_lipschitz(a, lo, max(cands), lip,
                            ms=_xmul(a.ms, a.mag * a.mag))


def _rule_integer_pow(ctx, eqn, ins):
    (a,) = ins
    y = eqn.params["y"]
    if y == 2:
        return _rule_square(ctx, eqn, ins)
    cands = [a.lo ** y, a.hi ** y]
    if y % 2 == 0 and a.lo <= 0.0 <= a.hi:
        lo = 0.0
    elif y % 2 == 1:
        lo = min(cands)
    else:
        lo = min(cands)
    lip = abs(y) * a.mag ** (y - 1) if a.mag != INF else INF
    return _unary_lipschitz(a, lo, max(cands), lip)


def _rule_rsqrt(ctx, eqn, ins):
    (a,) = ins
    if a.lo <= 0.0:
        ctx.report.add("info", "numerics/unbounded",
                       f"{ctx.location}: rsqrt",
                       "rsqrt over an interval touching zero; the static "
                       "bound is unbounded from this point on")
        return _top(set(a.err))
    return _unary_lipschitz(a, 1.0 / math.sqrt(a.hi) if a.hi != INF else 0.0,
                            1.0 / math.sqrt(a.lo), 0.5 * a.lo ** -1.5)


def _rule_sqrt(ctx, eqn, ins):
    (a,) = ins
    lo = math.sqrt(max(a.lo, 0.0))
    hi = math.sqrt(a.hi) if a.hi != INF else INF
    lip = INF if a.lo <= 0.0 else 0.5 / math.sqrt(a.lo)
    return _unary_lipschitz(a, lo, hi, lip)


def _rule_log(ctx, eqn, ins):
    (a,) = ins
    if a.lo <= 0.0:
        return _top(set(a.err))
    return _unary_lipschitz(a, math.log(a.lo),
                            math.log(a.hi) if a.hi != INF else INF,
                            1.0 / a.lo)


def _sigmoid(x: float) -> float:
    if x >= 0:
        return 1.0 / (1.0 + math.exp(-min(x, _EXP_CLAMP)))
    return math.exp(max(x, -_EXP_CLAMP)) / (
        1.0 + math.exp(max(x, -_EXP_CLAMP)))


def _rule_logistic(ctx, eqn, ins):
    (a,) = ins
    return _unary_lipschitz(a, _sigmoid(a.lo), _sigmoid(a.hi), 0.25)


def _rule_tanh(ctx, eqn, ins):
    (a,) = ins
    return _unary_lipschitz(a, max(-1.0, math.tanh(a.lo) if a.lo != -INF
                                   else -1.0),
                            min(1.0, math.tanh(a.hi) if a.hi != INF
                                else 1.0), 1.0)


def _rule_trig(ctx, eqn, ins):
    (a,) = ins
    return _unary_lipschitz(a, -1.0, 1.0, 1.0, ms=0.5)


def _rule_erf(ctx, eqn, ins):
    (a,) = ins
    return _unary_lipschitz(a, -1.0, 1.0, 2.0 / math.sqrt(math.pi))


def _rule_abs(ctx, eqn, ins):
    (a,) = ins
    lo = 0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi))
    return _unary_lipschitz(a, lo, a.mag, 1.0, ms=a.ms)


def _rule_neg(ctx, eqn, ins):
    (a,) = ins
    return ErrVal(lo=-a.hi, hi=-a.lo, err=dict(a.err), ms=a.ms,
                  err2=dict(a.err2))


def _rule_sign(ctx, eqn, ins):
    (a,) = ins
    err = {t: 2.0 for t, v in a.err.items() if v > 0.0}
    return ErrVal(lo=-1.0, hi=1.0, err=err, ms=1.0,
                  err2={t: 4.0 for t in err})


def _rule_round(ctx, eqn, ins):
    (a,) = ins
    err = {t: v + 1.0 for t, v in a.err.items() if v > 0.0}
    return ErrVal(lo=a.lo - 1.0, hi=a.hi + 1.0, err=err, ms=a.ms + 1.0,
                  err2={t: (v + 1.0) ** 2 if math.isfinite(v) else INF
                        for t, v in a.err2.items()})


def _rule_clamp(ctx, eqn, ins):
    amin, x, amax = ins
    lo = min(max(x.lo, amin.lo), amax.lo)
    hi = min(max(x.hi, amin.hi), amax.hi)
    return ErrVal(lo=lo, hi=hi, err=_esum(amin.err, x.err, amax.err),
                  ms=x.ms, err2=_esum(amin.err2, x.err2, amax.err2))


def _rule_bool(ctx, eqn, ins):
    err = {}
    for ev in ins:
        for t, v in ev.err.items():
            if v > 0.0:
                err[t] = 1.0
    return ErrVal(lo=0.0, hi=1.0, err=err, ms=0.5,
                  err2={t: 1.0 for t in err})


def _rule_int_bitwise(ctx, eqn, ins):
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    if not np.issubdtype(dt, np.integer):
        return _top(set().union(*(ev.err for ev in ins)))
    info = np.iinfo(dt)
    err = {t: INF for ev in ins for t, v in ev.err.items() if v > 0.0}
    return ErrVal(lo=float(info.min), hi=float(info.max), err=err,
                  err2=dict(err))


_RULES = {
    "add": _rule_add, "sub": _rule_add,
    "mul": _rule_mul,
    "div": _rule_div,
    "dot_general": _rule_dot, "conv_general_dilated": _rule_dot,
    "reduce_sum": _rule_reduce_sum,
    "reduce_max": _rule_reduce_minmax, "reduce_min": _rule_reduce_minmax,
    "cumsum": _rule_cumsum,
    "exp": _rule_exp, "exp2": _rule_exp,
    "max": _rule_elementwise_minmax, "min": _rule_elementwise_minmax,
    "select_n": _rule_select,
    "lt": _rule_compare, "le": _rule_compare, "gt": _rule_compare,
    "ge": _rule_compare, "eq": _rule_compare, "ne": _rule_compare,
    "broadcast_in_dim": _rule_pass, "reshape": _rule_pass,
    "transpose": _rule_pass, "squeeze": _rule_pass,
    "expand_dims": _rule_pass, "rev": _rule_pass, "slice": _rule_pass,
    "split": _rule_pass,
    "convert_element_type": _rule_pass, "copy": _rule_pass,
    "stop_gradient": _rule_pass, "dynamic_slice": _rule_pass,
    "real": _rule_pass, "imag": _rule_pass,
    "reduce_precision": _rule_pass,
    "all_gather": _rule_pass, "pmax": _rule_pass, "pmin": _rule_pass,
    "gather": _rule_gather,
    "concatenate": _rule_join, "dynamic_update_slice": _rule_join,
    "scatter": _rule_join,
    "pad": _rule_pad,
    "iota": _rule_iota,
    "square": _rule_square,
    "integer_pow": _rule_integer_pow,
    "rsqrt": _rule_rsqrt, "sqrt": _rule_sqrt,
    "log": _rule_log, "log1p": _rule_log,
    "logistic": _rule_logistic,
    "tanh": _rule_tanh,
    "sin": _rule_trig, "cos": _rule_trig,
    "erf": _rule_erf,
    "abs": _rule_abs,
    "neg": _rule_neg,
    "sign": _rule_sign,
    "floor": _rule_round, "ceil": _rule_round, "round": _rule_round,
    "clamp": _rule_clamp,
    "and": _rule_bool, "or": _rule_bool, "not": _rule_bool,
    "is_finite": _rule_bool, "reduce_and": _rule_bool,
    "reduce_or": _rule_bool,
    "xor": _rule_int_bitwise, "shift_left": _rule_int_bitwise,
    "shift_right_logical": _rule_int_bitwise,
    "shift_right_arithmetic": _rule_int_bitwise,
    "rem": _rule_int_bitwise,
}


def _rule_pow(ctx, eqn, ins):
    a, b = ins
    y = _scalar_const(ctx, eqn.invars[1])
    if y is not None and float(y).is_integer() and abs(y) < 64:
        fake = type("E", (), {"params": {"y": int(y)},
                              "invars": [eqn.invars[0]],
                              "outvars": eqn.outvars})
        return _rule_integer_pow(ctx, fake, [a])
    return _top(set(a.err) | set(b.err))


_RULES["pow"] = _rule_pow

_CALL_PRIMS = {"jit": "jaxpr", "remat2": "jaxpr", "closed_call": "jaxpr",
               "custom_jvp_call": "call_jaxpr",
               "custom_vjp_call": "call_jaxpr",
               "custom_vjp_call_jaxpr": "fun_jaxpr"}


# ---------------------------------------------------------------------------
# the walker


def _closed_parts(obj):
    if hasattr(obj, "jaxpr") and hasattr(obj.jaxpr, "eqns"):
        return obj.jaxpr, list(getattr(obj, "consts", ()) or ())
    return obj, []


def _seed_consts(ctx: _Ctx, jaxpr, consts) -> None:
    for cv, c in zip(jaxpr.constvars, consts):
        try:
            ctx.env[cv] = _from_array(c)
        except (TypeError, ValueError):
            ctx.env[cv] = ErrVal()


def _is_float_atom(atom) -> bool:
    return np.issubdtype(np.dtype(atom.aval.dtype), np.floating)


def _in_tags(ins) -> set:
    tags: set = set()
    for ev in ins:
        tags.update(t for t, v in ev.err.items() if v > 0.0)
        if ev.payload is not None:
            tags.update(ev.payload)
    return tags


def _assign_top(ctx: _Ctx, eqn, ins) -> None:
    top = _top(_in_tags(ins))
    for ov in eqn.outvars:
        ctx.env[ov] = top


def _is_neutral(atom, ev: ErrVal) -> bool:
    """Decode-plumbing operands don't break payload purity: integer/bool
    consts (shift counts, gather indices, bit masks) and uniform-valued
    float consts (fill values, scaling literals).  A non-uniform float
    operand is program data — mixing with it materializes the payload."""
    if ev.const is None or not ev.exact():
        return False
    if not _is_float_atom(atom):
        return True
    c = np.asarray(ev.const)
    return c.size <= 1 or float(c.min()) == float(c.max())


def _try_const(ctx: _Ctx, eqn, ins, out: ErrVal) -> ErrVal:
    if eqn.primitive.multiple_results:
        return out
    if any(ev.const is None or not ev.exact() for ev in ins):
        return out
    try:
        out_size = int(np.prod(eqn.outvars[0].aval.shape))
    except (AttributeError, TypeError):
        return out
    if out_size > _CONST_SIZE_LIMIT:
        return out
    try:
        res = eqn.primitive.bind(*[ev.const for ev in ins], **eqn.params)
        ev = _from_array(res)
    except Exception:
        return out
    ev.err, ev.err2 = out.err, out.err2
    return ev


def _inline_call(ctx: _Ctx, eqn, sub) -> None:
    jx, consts = _closed_parts(sub)
    _seed_consts(ctx, jx, consts)
    for iv, atom in zip(jx.invars, eqn.invars):
        ctx.env[iv] = _read(ctx, atom)
        if not _is_literal(atom):
            ctx.alias[iv] = atom
    _walk_eqns(ctx, jx)
    for ov, sub_ov in zip(eqn.outvars, jx.outvars):
        ctx.env[ov] = _read(ctx, sub_ov)
        if not _is_literal(sub_ov):
            ctx.alias[ov] = sub_ov


def _walk_branch(ctx: _Ctx, branch, operand_atoms) -> list:
    jx, consts = _closed_parts(branch)
    _seed_consts(ctx, jx, consts)
    for iv, atom in zip(jx.invars, operand_atoms):
        ctx.env[iv] = _read(ctx, atom)
        if not _is_literal(atom):
            ctx.alias[iv] = atom
    _walk_eqns(ctx, jx)
    return [_read(ctx, ov) for ov in jx.outvars]


def _eqn_cond(ctx: _Ctx, eqn) -> None:
    idx = _read(ctx, eqn.invars[0])
    branches = eqn.params["branches"]
    ops = eqn.invars[1:]
    if (idx.const is not None and idx.exact()
            and np.asarray(idx.const).size == 1):
        b = int(np.clip(int(np.asarray(idx.const).reshape(())), 0,
                        len(branches) - 1))
        outs = _walk_branch(ctx, branches[b], ops)
    else:
        per_branch = [_walk_branch(ctx, br, ops) for br in branches]
        outs = [_join_vals([pb[i] for pb in per_branch])
                for i in range(len(eqn.outvars))]
        utags = {t for t, v in idx.err.items() if v > 0.0}
        for ev in outs:
            w = ev.width
            for t in utags:
                ev.err[t] = ev.err.get(t, 0.0) + w
                ev.err2[t] = ev.err2.get(t, 0.0) + (
                    w * w if math.isfinite(w) else INF)
            ev.const = None
    for ov, ev in zip(eqn.outvars, outs):
        ctx.env[ov] = ev


def _slice_lead(ev: ErrVal, i: int) -> ErrVal:
    if ev.payload is not None or ev.const is None or not ev.exact():
        return ev
    c = np.asarray(ev.const)
    if c.ndim == 0:
        return ev
    out = _from_array(c[i])
    out.err2 = dict(ev.err2)
    return out


def _eqn_scan(ctx: _Ctx, eqn, ins) -> None:
    p = eqn.params
    length, nc, ncar = p["length"], p["num_consts"], p["num_carry"]
    if length > ctx.unroll_limit:
        ctx.note_unsupported(
            "scan", f"scan of length {length} exceeds the unroll limit "
            f"({ctx.unroll_limit}); bound is unconstrained downstream")
        _assign_top(ctx, eqn, ins)
        return
    jx, consts = _closed_parts(p["jaxpr"])
    const_ins, carry, xs = ins[:nc], list(ins[nc:nc + ncar]), ins[nc + ncar:]

    if id(eqn) not in ctx.flash_cache:
        try:
            ctx.flash_cache[id(eqn)] = _match_flash_scan(eqn)
        except Exception:
            ctx.flash_cache[id(eqn)] = None
    flash = ctx.flash_cache[id(eqn)]
    flash_live = False
    m0_hi = INF
    if flash is not None:
        l0, m0 = ins[nc + flash.l_pos], ins[nc + flash.m_pos]
        flash_live = (l0.const is not None and l0.exact()
                      and not np.any(np.asarray(l0.const))
                      and m0.const is not None and m0.exact())
        if flash_live:
            m0_hi = float(np.max(np.asarray(m0.const).astype(np.float64)))
    taken, min_x_lo = 0, INF

    n_ys = len(eqn.outvars) - ncar
    ys_acc: list = [[None] * length for _ in range(n_ys)]
    order = range(length - 1, -1, -1) if p.get("reverse") else range(length)
    _seed_consts(ctx, jx, consts)
    for iv, atom, ev in zip(jx.invars[:nc], eqn.invars[:nc], const_ins):
        ctx.env[iv] = ev
        if not _is_literal(atom):
            ctx.alias[iv] = atom
    for i in order:
        for iv, ev in zip(jx.invars[nc:nc + ncar], carry):
            ctx.env[iv] = ev
        for iv, ev in zip(jx.invars[nc + ncar:], xs):
            ctx.env[iv] = _slice_lead(ev, i)
        _walk_eqns(ctx, jx)
        outs = [_read(ctx, ov) for ov in jx.outvars]
        carry = outs[:ncar]
        for k, ev in enumerate(outs[ncar:]):
            ys_acc[k][i] = ev
        if flash is not None and flash_live:
            pev = _read(ctx, flash.cond_eqn.invars[0])
            if (pev.const is None or not pev.exact()
                    or np.asarray(pev.const).size != 1):
                flash_live = False
            elif int(np.asarray(pev.const).reshape(())) \
                    == flash.update_branch:
                xev = ctx.env.get(flash.x_var)
                if xev is None:
                    flash_live = False
                else:
                    taken += 1
                    min_x_lo = min(min_x_lo, xev.lo)
    if flash is not None and flash_live and taken >= 1 \
            and m0_hi <= min_x_lo:
        lv = carry[flash.l_pos]
        carry[flash.l_pos] = dataclasses.replace(
            lv, lo=max(lv.lo, 1.0), const=None)
    ys = [_join_vals(col) for col in ys_acc]
    for ov, ev in zip(eqn.outvars, carry + ys):
        ctx.env[ov] = ev


def _eqn(ctx: _Ctx, eqn) -> None:
    prim = eqn.primitive.name
    for ov in eqn.outvars:
        ctx.defs[ov] = eqn
    if prim in _CALL_PRIMS:
        sub = eqn.params.get(_CALL_PRIMS[prim])
        if sub is None:
            sub = next((v for v in eqn.params.values()
                        if hasattr(v, "eqns")
                        or (hasattr(v, "jaxpr")
                            and hasattr(v.jaxpr, "eqns"))), None)
        if sub is not None:
            _inline_call(ctx, eqn, sub)
            return
    ins = [_read(ctx, a) for a in eqn.invars]
    if prim == "scan":
        _eqn_scan(ctx, eqn, ins)
        return
    if prim == "cond":
        _eqn_cond(ctx, eqn)
        return
    if prim in ("while", "pallas_call"):
        ctx.note_unsupported(
            prim, "not interpreted; bound is unconstrained downstream")
        _assign_top(ctx, eqn, ins)
        return

    if any(ev.payload is not None for ev in ins):
        mixing = any(ev.payload is None and not _is_neutral(atom, ev)
                     for atom, ev in zip(eqn.invars, ins))
        if not mixing:
            tags = frozenset().union(*(ev.payload for ev in ins
                                       if ev.payload is not None))
            out = ErrVal(payload=tags)
            for ov in eqn.outvars:
                ctx.env[ov] = out
            return
        new_ins = []
        for atom, ev in zip(eqn.invars, ins):
            if ev.payload is None:
                new_ins.append(ev)
                continue
            tag = next(iter(ev.payload)) if len(ev.payload) == 1 else None
            if (tag is not None and tag in ctx.stats
                    and not _is_literal(atom) and _is_float_atom(atom)):
                new_ins.append(_from_stats(ctx.stats[tag], tag))
            else:
                ctx.note_unsupported(
                    prim, "packed payload mixes with program values "
                    "before decode completes")
                _assign_top(ctx, eqn, ins)
                return
        ins = new_ins

    rule = _RULES.get(prim)
    if rule is None:
        ctx.note_unsupported(
            prim, "no transfer rule; bound is unconstrained downstream")
        _assign_top(ctx, eqn, ins)
        return
    out = rule(ctx, eqn, ins)
    out = _try_const(ctx, eqn, ins, out)
    _cap(out)
    for ov in eqn.outvars:
        ctx.env[ov] = out


def _walk_eqns(ctx: _Ctx, jaxpr) -> None:
    for eqn in jaxpr.eqns:
        _eqn(ctx, eqn)


# ---------------------------------------------------------------------------
# public API


@dataclasses.dataclass
class NumericsResult:
    """Statically derived output-error bounds of one traced program."""

    per_tag: dict        # payload leaf -> sound output-error bound
    total: float         # sound end-to-end bound (all leaves quantized)
    per_tag_err2: dict   # payload leaf -> estimated output-error power
    total_err2: float
    interval: tuple      # joint output interval (lo, hi)
    unsupported: tuple   # primitives the interpreter gave up on

    def to_json(self) -> dict:
        return {"per_tag": {t: float(v) for t, v in self.per_tag.items()},
                "total": float(self.total),
                "per_tag_err2": {t: float(v)
                                 for t, v in self.per_tag_err2.items()},
                "total_err2": float(self.total_err2),
                "interval": [float(self.interval[0]),
                             float(self.interval[1])],
                "unsupported": list(self.unsupported)}


def _match_suffix(names: list, table: dict) -> Optional[str]:
    """Resolve a leaf path against plan-entry / seed names, tolerating the
    argument-position prefix ``tree_leaves_with_path`` adds (``0/...``)."""
    for i in range(len(names)):
        cand = "/".join(names[i:])
        if cand in table:
            return cand
    return None


def _seed_leaf(ctx: _Ctx, path, leaf) -> ErrVal:
    from repro.analysis.dataflow import _key_name
    names = [_key_name(p) for p in path]
    field = names[-1] if names else ""
    if field in PAYLOAD_KEYS or field == SCALE_KEY:
        tag = _match_suffix(names[:-1], ctx.stats)
        if tag is not None:
            return ErrVal(payload=frozenset({tag}))
    full = _match_suffix(names, ctx.seeds)
    if full is not None:
        s = ctx.seeds[full]
        base = _from_array(leaf)
        return ErrVal(lo=base.lo - s.err, hi=base.hi + s.err,
                      err={full: s.err} if s.err else {}, ms=base.ms,
                      err2={full: s.err2} if s.err2 else {},
                      const=base.const if s.err == 0.0 else None)
    try:
        return _from_array(leaf)
    except (TypeError, ValueError):
        return ErrVal()


def analyze(fn, *args, stats=None, seeds=None, location: str = "<fn>",
            scan_unroll_limit: int = _SCAN_UNROLL_LIMIT, **kwargs):
    """Abstractly interpret ``fn(*args, **kwargs)`` and return
    ``(NumericsResult, Report)``.

    ``stats`` maps payload leaf names (plan-entry names) to
    :class:`LeafStats` — usually :func:`leaf_stats_from_plan`.  ``seeds``
    maps ordinary (float) leaf path names to :class:`LeafStats` whose
    ``err``/``err2`` are injected at that input — the mechanism behind
    :func:`output_gains`."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    leaves = jax.tree_util.tree_leaves_with_path((args, kwargs))
    report = Report()
    ctx = _Ctx(stats=dict(stats or {}), report=report, location=location,
               unroll_limit=scan_unroll_limit, seeds=dict(seeds or {}))
    _seed_consts(ctx, closed.jaxpr, closed.consts)
    for var, (path, leaf) in zip(closed.jaxpr.invars, leaves):
        ctx.env[var] = _seed_leaf(ctx, path, leaf)
    _walk_eqns(ctx, closed.jaxpr)
    joined = _join_vals([_read(ctx, ov) for ov in closed.jaxpr.outvars])
    if joined.payload is not None:
        joined = _top(joined.payload)
    result = NumericsResult(
        per_tag={t: float(v) for t, v in sorted(joined.err.items())},
        total=float(joined.total_err()),
        per_tag_err2={t: float(v) for t, v in sorted(joined.err2.items())},
        total_err2=float(sum(joined.err2.values())),
        interval=(joined.lo, joined.hi),
        unsupported=tuple(sorted(ctx.unsupported)))
    return result, report


def output_gains(fn, *args, names, location: str = "<fn>", **kwargs) -> dict:
    """Per-leaf output noise gains: run one :func:`analyze` pass over the
    float program with a unit mean-square error seeded at every leaf in
    ``names``.  The unit seed saturates at the leaf's own range (``err2``
    is width^2-capped, see :func:`_cap`), so the output ``err2`` per leaf
    is that leaf's *range-aware* gain ``G`` — the response to full-range
    noise at that tensor; seeds small against every interval they cross
    propagate linearly instead.  Predicted output error power for a
    schedule is scored as ``G * noise_power(cfg)``."""
    seeds = {n: LeafStats(lo=0.0, hi=0.0, err=0.0, err2=1.0, ms=0.0)
             for n in names}
    res, _ = analyze(fn, *args, seeds=seeds, location=location, **kwargs)
    return {n: float(res.per_tag_err2.get(n, 0.0)) for n in names}


def measured_error(fn, args_a, args_b) -> float:
    """Teacher-forced measured output error: ``max |fn(*args_a) -
    fn(*args_b)|`` over all output leaves."""
    ya = jax.tree_util.tree_leaves(fn(*args_a))
    yb = jax.tree_util.tree_leaves(fn(*args_b))
    worst = 0.0
    for a, b in zip(ya, yb):
        d = np.asarray(a, dtype=np.float64) - np.asarray(b,
                                                         dtype=np.float64)
        if d.size:
            worst = max(worst, float(np.max(np.abs(d))))
    return worst


def check_error_budget(result: NumericsResult, budget: dict,
                       location: str = "<schedule>") -> Report:
    """Compare a :class:`NumericsResult` against a declared error budget
    (``{"total": x, "per_layer": y-or-{name: y}}``); every violation is a
    ``numerics/budget-exceeded`` error finding."""
    report = Report()
    total_cap = budget.get("total")
    if total_cap is not None and result.total > float(total_cap):
        report.add("error", "numerics/budget-exceeded", location,
                   f"static end-to-end output-error bound {result.total:.6g}"
                   f" exceeds the declared total budget {total_cap:.6g}")
    per = budget.get("per_layer")
    if per is not None:
        caps = per if isinstance(per, dict) else {
            t: float(per) for t in result.per_tag}
        for t, cap in sorted(caps.items()):
            bound = result.per_tag.get(t)
            if bound is not None and bound > float(cap):
                report.add("error", "numerics/budget-exceeded",
                           f"{location}: {t}",
                           f"static per-layer bound {bound:.6g} exceeds "
                           f"the declared per-layer budget {float(cap):.6g}")
    return report
