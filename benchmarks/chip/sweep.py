#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest session-start
rate at which the backlog of first tokens does not grow.  Run once, on
the chip, when a cell is defined; the cell's traffic file then fixes its
rate at about 0.8 of the knee.

    python3 benchmarks/chip/sweep.py --workload phi3-chat-sessions \
        --seed 7 --seconds 40 --rates 0.3 0.5 0.7 0.9

One process: weights, engine and warm-up once, then one window per rate
(the engine drains between windows).  For each rate it prints the TTFT
median and p95, and the TTFT median of the requests due in each third
of the window: a backlog that grows shows as a rising third.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchmarks.chip import bench, serving, traffic
    spec = bench.load_spec()
    cell, cfg, mix = bench.cell_files(spec, args.workload)
    bench.enable_compile_cache()
    bench.device_info(cell["chips"])
    mix0 = dict(mix, rate_per_s=args.rates[0])
    eng = serving.prepare(cfg, mix0, args.seed, args.seconds,
                          time.perf_counter())[0].eng
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        sched = traffic.sessions(m, args.seed, args.seconds,
                                 cfg["vocab_size"], cfg["max_len"])
        win = serving.Window(eng)
        win.run_sessions(sched, args.seconds)
        e2e = win.end_to_end(open_loop=True)
        reqs = win.tracker.all
        thirds = []
        for k in range(3):
            lo = win.t0 + k * args.seconds / 3
            hi = lo + args.seconds / 3
            t = [r["stamps"][0] - r["due"] if r["stamps"] else float("inf")
                 for r in reqs if lo <= r["due"] < hi]
            thirds.append(bench.quantile(t, 0.5) * 1e3 if t else None)
        print(f"[sweep] rate {rate}/s: {e2e['requests']} requests, "
              f"{e2e['failed']} failed; ttft median "
              f"{bench.quantile(e2e['ttft'], 0.5) * 1e3:.1f} ms, p95 "
              f"{e2e['ttft_p95_ms']:.1f} ms; by third of the window "
              f"{thirds}; itl p95 {e2e['itl_p95_ms']:.1f} ms; "
              f"{e2e['tokens_per_s']:.1f} tokens/s", flush=True)
        eng.run(max_ticks=1_000_000)
    return 0


if __name__ == "__main__":
    sys.exit(main())
