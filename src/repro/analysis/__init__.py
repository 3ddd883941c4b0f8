"""repro.analysis — static analysis passes for the StruM engine.

Four trace-time passes prove engine invariants without running a kernel:

* **packed dataflow** (:func:`verify`, :func:`trace_dataflow`) — taint
  analysis over jaxprs proving collectives move only packed payload bytes
  (the Eq.-1 ratio), payloads decode exactly once, and no fp bytes leak
  out of sealed cache pages;
* **registry audit** (:func:`audit_registry`) — sweeps the capability
  grid and flags unreachable, shadowed, or overlapping kernel variants;
* **Pallas lint** (:func:`lint_pallas`) — abstract-evals every
  ``pallas:*`` / ``cache:*`` variant against its tiling contracts;
* **recompile lint** (:func:`lint_scheduler_recompiles`) — proves each
  serving lane compiles exactly one executable across prompt lengths (the
  sealer one per source shape).

``python -m repro.analysis`` runs them over the built-in model zoo; the
module import is jax-free (findings/rules only) and heavy passes load
lazily so ``--list-rules`` works without configuring a backend.
"""
from repro.analysis.report import RULES, SEVERITIES, Finding, Report

__all__ = [
    "Finding", "Report", "RULES", "SEVERITIES",
    "verify", "trace_dataflow", "collective_stats",
    "audit_registry", "render_coverage",
    "lint_pallas", "lint_scheduler_recompiles",
    "validate_plan", "run_all",
]

_LAZY = {
    "verify": "repro.analysis.dataflow",
    "trace_dataflow": "repro.analysis.dataflow",
    "collective_stats": "repro.analysis.dataflow",
    "audit_registry": "repro.analysis.registry_audit",
    "render_coverage": "repro.analysis.registry_audit",
    "lint_pallas": "repro.analysis.pallas_lint",
    "lint_scheduler_recompiles": "repro.analysis.recompile",
    "validate_plan": "repro.analysis.plan_check",
    "run_all": "repro.analysis.suite",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.analysis' has no attribute "
                             f"{name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)
