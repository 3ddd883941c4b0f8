#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload olmo_1b.chat --seed 7 --seconds 40 --trace 0

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` prints its per-layer metrics, the device's busy time and a
breakdown of the profiled window.  Either way the run ends with a check of
the served tokens against the plain reference, and the last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` [, ``breakdown``], ``check``).

The run refuses, with no result, where JAX finds no TPU, fewer chips than
the cell asks for, or a device kind with no published peaks.  JAX's
persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``bench/.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, "bench", ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import harness, peaks

    cell = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        peaks.for_device(devs[0])
    except KeyError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = harness.run_cell(cell, harness.Options(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        keep_trace=args.keep_trace, t_start=T_START))
    extra = out.pop("_extra")
    s = extra["samples"]
    print(f"samples: {s['tokens']} output tokens, {s['ttft']} TTFT, "
          f"{s['itl']} ITL over a {s['window_s']:.3f}s window; "
          f"{out['attempted']} requests attempted, {out['failed']} failed; "
          f"{extra['checked_requests']} requests checked; programs built or "
          f"loaded in set-up {extra['compiles_setup']} "
          f"({extra['cache_hits_setup']} from the compile cache), in window "
          f"{extra['compiles_window']}; reference {extra['reference_s']:.1f}s",
          flush=True)
    print(json.dumps({"e2e": extra["e2e"],
                      "per_request_gap": extra["compare"].get("per_request")}),
          file=sys.stderr, flush=True)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
