#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the machine it starts on.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are found by name in BENCHMARK.json (see bench.py).
With --trace 0 the result line carries the cell's end-to-end metrics;
with --trace 1 its per-layer metrics, read from a profiler trace of a
part of the window and from the program's counters.

Standard error gets the set-up, the window's medians and sample counts,
the count of compiles inside the window, and last the numbers compared
for `correct` beside their limits.  The last line of standard output is
one JSON object: correct, attempted, failed, metrics, device
(breakdown with --trace 1), then `compared`.

Exits 1, printing no result, when JAX's first device is not a TPU or
there are fewer chips than the cell asks for, and 2 on any other error.
"""
import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks.chip import bench
    spec = bench.load_spec()
    cell, cfg, mix = bench.cell_files(spec, args.workload)
    metrics = bench.cell_metrics(spec, args.workload, bool(args.trace))
    harness = bench.load_module(f"{cfg['kind']}.py")
    bench.enable_compile_cache()
    bench.phase("imports", T_PROCESS)
    device = bench.device_info(cell["chips"])
    bench.phase("device", T_PROCESS)
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", file=sys.stderr, flush=True)
    meter = bench.CompileMeter()
    res = harness.run_cell(cell, cfg, mix, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_process=T_PROCESS, meter=meter, device=device,
                           spec_metrics=metrics)
    meter.close()
    for line in bench.compared_lines(res["compared"]):
        print(line, file=sys.stderr, flush=True)
    print(bench.result_line(**res), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception as e:  # noqa: BLE001 -- the run's one boundary
        traceback.print_exc()
        from_no_tpu = type(e).__name__ == "NoAccelerator"
        rc = 1 if from_no_tpu else 2
    sys.stdout.flush()
    sys.exit(rc)
