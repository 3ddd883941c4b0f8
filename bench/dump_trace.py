#!/usr/bin/env python3
"""Summarise a profiler trace: planes, lines, event counts and a few events
with their stats, to read how a device names its operations.

    python3 bench/dump_trace.py <trace dir> [--out summary.json] [--flat f.json]

``--flat`` also writes the trace as ``bench.lib.trace.flatten`` reduces it.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("dir")
    ap.add_argument("--out", default=None)
    ap.add_argument("--flat", default=None)
    ap.add_argument("--events", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from jax.profiler import ProfileData

    from bench.lib import trace as T

    files = glob.glob(os.path.join(args.dir, "**", "*.xplane.pb"),
                      recursive=True)
    prof = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in prof.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            sample = []
            for ev in evs[:args.events]:
                st = {k: (v[:400] if isinstance(v, str) else v)
                      for k, v in dict(ev.stats).items()}
                sample.append({"name": ev.name, "start_ns": ev.start_ns,
                               "dur_ns": ev.duration_ns, "stats": st})
            lines.append({"line": line.name, "events": len(evs),
                          "sample": sample})
        out.append({"plane": plane.name, "lines": lines})
    text = json.dumps(out, indent=1, default=str)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)
    if args.flat:
        with open(args.flat, "w") as f:
            json.dump(T.flatten(prof), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
