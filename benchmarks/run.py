"""Benchmark entry point: one section per paper table/figure + the
roofline table + the serving benchmark.

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --sections fig9_rodinia,serving

Every run also emits machine-readable artifacts (so the perf trajectory
is tracked across PRs) into `--out-dir` (default `bench_out/`, override
with REPRO_BENCH_OUT):

  BENCH_fig9_rodinia.json   per-(bench, config) SIMT stats + PerfReports
  BENCH_serving.json        chunked-prefill / prefix-cache serving gate
  BENCH_run.json            section wall times + global metrics snapshot
  run.trace.json            Chrome/Perfetto trace of the whole run

CI's bench-gate job runs the fig9_rodinia and serving sections and diffs
their artifacts against benchmarks/baselines/ via `benchmarks.diff`.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro import obs
from repro.launch.compile_cache import enable_compile_cache

SECTIONS = ("fig8_dse", "fig9_rodinia", "fig10_power", "roofline_table",
            "serving")


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir",
                    default=os.environ.get("REPRO_BENCH_OUT", "bench_out"))
    ap.add_argument("--sections", default=",".join(SECTIONS),
                    help="comma-separated subset of: " + ",".join(SECTIONS))
    args = ap.parse_args(argv)
    sections = [s for s in args.sections.split(",") if s]
    unknown = sorted(set(sections) - set(SECTIONS))
    if unknown:
        ap.error(f"unknown sections: {unknown} (choose from {SECTIONS})")
    os.makedirs(args.out_dir, exist_ok=True)
    obs.enable_tracing()

    t0 = time.time()
    section_s = {}

    def run_section(name, fn):
        if name not in sections:
            return
        with obs.trace.span(name):
            ts = time.time()
            fn()
            section_s[name] = time.time() - ts

    def fig8():
        print("==== Fig 8: area/power design-space (synthesis model) ====")
        from benchmarks import fig8_dse
        fig8_dse.main()

    fig9_stats = {}

    def fig9():
        print("\n==== Fig 9: Rodinia cycles over (warps x threads) ====")
        from benchmarks import fig9_rodinia
        stats = fig9_rodinia.run_all()
        fig9_rodinia.print_table(stats)
        fig9_stats["stats"] = stats
        with open(os.path.join(args.out_dir, "BENCH_fig9_rodinia.json"),
                  "w") as f:
            json.dump(fig9_rodinia.results_doc(stats), f, indent=1)

    def fig10():
        print("\n==== Fig 10: power efficiency ====")
        from benchmarks import fig10_power
        # reuses fig9 stats when that section ran, recomputes otherwise
        fig10_power.main(stats=fig9_stats.get("stats"))

    def roofline():
        print("\n==== Roofline table (from dry-run artifacts) ====")
        from benchmarks import roofline_table
        roofline_table.main()

    def serving():
        print("\n==== Serving: chunked prefill + prefix cache ====")
        from benchmarks import serving as serving_bench
        serving_bench.main(out_dir=args.out_dir)

    run_section("fig8_dse", fig8)
    run_section("fig9_rodinia", fig9)
    run_section("fig10_power", fig10)
    run_section("roofline_table", roofline)
    run_section("serving", serving)

    wall = time.time() - t0
    with open(os.path.join(args.out_dir, "BENCH_run.json"), "w") as f:
        json.dump({"total_wall_s": wall, "sections_wall_s": section_s,
                   "metrics": obs.metrics.snapshot()}, f, indent=1)
    trace_path = os.path.join(args.out_dir, "run.trace.json")
    obs.write_chrome_trace(trace_path, obs.tracer.drain())
    print(f"\n# artifacts in {args.out_dir}/ "
          f"(BENCH_*.json + run.trace.json — load in Perfetto)")
    print(f"# total benchmark wall time {wall:.0f}s")


if __name__ == "__main__":
    main()
