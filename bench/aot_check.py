#!/usr/bin/env python3
"""Compile a cell's decode and prefill lanes for a described TPU v5e chip,
at the cell's real shapes, without a chip.

    JAX_PLATFORMS=cpu python3 bench/aot_check.py [--workload NAME ...]

Catches what the chip's compiler would refuse (Mosaic lowering, tiling,
VMEM, a program that does not fit the device) before any chip time is
spent.  It compiles; it runs nothing, and prints no time.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lanes(cell, chip):
    """(name, fn, abstract args) of the scheduler's two lanes."""
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import (make_chunked_prefill_step,
                                    make_paged_decode_step)
    from repro.serving import pages

    from bench.lib import harness

    config, traffic = cell.config, cell.traffic
    s = config["serve"]
    n = int(traffic["n_slots"])
    pp = pages.pages_per_seq(int(traffic["max_len"]), int(s["page_size"]))

    def build():
        return harness.build_plan(config, 0, backend="pallas")[0].params

    mcfg = harness.model_config(config)
    spec = pages.make_cache_spec(mcfg, harness.codec(s["kv"]),
                                 int(s["page_size"]), backend="pallas")
    params = jax.eval_shape(build)
    pools = jax.eval_shape(lambda: pages.init_pools(mcfg, n * pp, spec))
    hot = jax.eval_shape(lambda: pages.init_hot(mcfg, n, int(s["page_size"])))

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    params, pools, hot = place(params), place(pools), place(hot)
    decode = make_paged_decode_step(mcfg, spec, cache_backend="pallas")
    prefill = make_chunked_prefill_step(mcfg, spec, cache_backend="pallas")
    i32 = jnp.int32
    yield "decode", decode, (params, arr((n, 1), i32), pools, hot,
                             arr((n,), i32), arr((n, pp), i32),
                             arr((n,), jnp.bool_))
    yield "prefill", prefill, (params, arr((1, int(s["prefill_chunk"])), i32),
                               pools, hot, arr((n, pp), i32), arr((), i32),
                               arr((), i32), arr((), i32))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the program reads this: Pallas lowers for the chip, not interpreted
    os.environ["STRUM_INTERPRET"] = "0"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.lib import harness

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    names = args.workload or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bad = 0
    for name in names:
        cell = harness.load_cell(name)
        for lane, fn, shapes in lanes(cell, chip):
            try:
                compiled = jax.jit(fn).lower(*shapes).compile()
            except Exception as e:          # report every refusal, go on
                bad += 1
                print(f"{name} {lane}: REFUSED: {str(e)[:2000]}", flush=True)
                continue
            mem = compiled.memory_analysis()
            kernels = compiled.as_text().count("tpu_custom_call")
            print(f"{name} {lane}: compiled, {kernels} Mosaic kernel calls, "
                  f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
                  f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB",
                  flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
