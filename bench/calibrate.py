#!/usr/bin/env python3
"""Read the check's two readings on the chip: the program's widest gap and
the control's, for many seeds of one cell, in one process.

    python3 bench/calibrate.py --workload olmo_1b.chat --seconds 40 \\
        --seeds 11,12,13

Each seed is a whole run at the cell's own size and load (set-up from the
seed, the closed-loop window, the check), with the control (the reference
computed in fp8) read over the same served tokens: its first choices take
the served tokens' place in the harness's own ``correct`` expression.  One
JSON line per seed.  Exits 1 unless every seed's run is correct and every
seed's control is not.  The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench.lib import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, "bench", ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, harness.Options(
            seed=seed, seconds=args.seconds, control=True,
            t_start=time.perf_counter()))
        ex = out["_extra"]
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "control_correct": ex["control_correct"],
            "max_gap": ex["compare"]["max_gap"],
            "control_max_gap": ex["compare"]["control_max_gap"],
            "top1_agree": ex["compare"]["top1_agree"],
            "per_request": ex["compare"]["per_request"],
            "control_per_request": ex["compare"]["control_per_request"],
            "checked_tokens": out["check"]["checked_tokens"]["value"],
            "failed": out["failed"], "e2e": ex["e2e"], "memory": ex["memory"],
            "setup_s": ex["setup_s"], "reference_s": ex["reference_s"],
            "compiles_window": ex["compiles_window"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
        sound &= out["correct"] and ex["control_correct"] is False
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
