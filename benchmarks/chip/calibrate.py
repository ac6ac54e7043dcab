#!/usr/bin/env python3
"""Readings that a serving cell's correctness limit is set from, on the
chip, in one process: for each seed, a window of the cell's own traffic
at its own load, then on the same sample of finished requests

  program  the widest gap by which a served token's float32 reference
           logit lies below the reference's best (what every run
           compares), and
  control  the same gap for the token that the reference computed in
           float8 (the step below the configuration's bfloat16) puts
           first at each of those positions.

    python3 benchmarks/chip/calibrate.py --workload phi3-decode-long \
        --seconds 30 --seeds 11 12 13

The limit lies between the largest program reading over a dozen seeds
or more and the smallest control reading (limits/<workload>.json).
"""
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--min-tokens", type=int, default=100,
                    help="served tokens in finished requests a closed-loop "
                    "window steps on for (the cell's min_checked_tokens)")
    args = ap.parse_args(argv)
    from benchmarks.chip import bench, serving
    spec = bench.load_spec()
    cell, cfg, mix = bench.cell_files(spec, args.workload)
    bench.enable_compile_cache()
    bench.device_info(cell["chips"])
    rows = []
    for seed in args.seeds:
        row = {"seed": seed, **serving.readings(
            cfg, mix, seed, args.seconds, min_done_tokens=args.min_tokens)}
        print("[calibrate] " + json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    print("[calibrate] " + json.dumps({
        "program_max": max(r["program"]["max_gap"] for r in rows),
        "control_min": min(r["control"]["max_gap"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
