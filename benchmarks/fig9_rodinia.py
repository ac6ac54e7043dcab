"""Fig 9 reproduction: Rodinia-subset cycle counts over (warps x threads),
normalized to the 2w x 2t config (the paper's normalization).

Regular kernels run in the paper's warmed-cache regime; BFS runs its
full-size (cache-exceeding) graph — §V-D's two regimes.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Tuple

from repro import obs
from repro.core.simt.machine import MachineConfig
from repro.runtime.kernels_src import rodinia

CONFIGS = [(2, 2), (2, 8), (8, 2), (8, 8), (4, 16), (16, 4)]

BENCHES: Dict[str, Tuple[dict, int]] = {
    # name -> (kwargs, miss_latency)
    "vecadd": (dict(n=256), 16),
    "saxpy": (dict(n=256, repeats=8), 16),
    "sgemm": (dict(m=12, k=12, n=12), 16),
    # graph > 4 KB dcache: the latency-bound regime where warps pay off
    # (smaller graphs fit the cache and flip the Fig-10 BFS optimum)
    "bfs": (dict(n_nodes=512, avg_deg=4), 200),
    "gaussian": (dict(n=16), 16),
    "nn": (dict(n=256), 16),
    "kmeans": (dict(n=128, k=8), 16),
}


def machine_config(warps: int, threads: int,
                   miss_latency: int) -> MachineConfig:
    """The machine every Fig-9 bench runs on."""
    return MachineConfig(warps=warps, threads=threads, max_cycles=12_000_000,
                         miss_latency=miss_latency)


def run_all(configs=CONFIGS, benches=BENCHES):
    """-> {(bench, warps, threads): stats-dict}."""
    out = {}
    for name, (kw, ml) in benches.items():
        for w, t in configs:
            mc = machine_config(w, t, ml)
            with obs.trace.span(f"simt:{name}", warps=w, threads=t):
                res, ok = rodinia.BENCHMARKS[name](mc, **kw)
            assert ok, f"{name} failed verification at {w}x{t}"
            out[(name, w, t)] = res.stats
    return out


def print_table(stats, configs=CONFIGS, benches=BENCHES):
    print("bench,config,cycles,normalized_to_2x2,instrs,dcache_miss_rate")
    for name in benches:
        base = stats[(name, 2, 2)]["cycles"]
        for w, t in configs:
            s = stats[(name, w, t)]
            mr = s["dcache_misses"] / max(
                s["dcache_misses"] + s["dcache_hits"], 1)
            print(f"{name},{w}w{t}t,{s['cycles']},"
                  f"{s['cycles']/base:.3f},{s['instrs']},{mr:.3f}")


def results_doc(stats) -> dict:
    """Machine-readable results: raw stats + derived PerfReport per
    (bench, config), keyed 'bench/4w8t'."""
    out = {}
    for (name, w, t), s in stats.items():
        rep = obs.PerfReport.from_stats(s, warps=w, threads=t)
        out[f"{name}/{w}w{t}t"] = {"stats": dict(s),
                                   "perf": rep.as_dict()}
    return out


def main(out_dir=None):
    out_dir = out_dir or os.environ.get("REPRO_BENCH_OUT", "bench_out")
    os.makedirs(out_dir, exist_ok=True)
    obs.enable_tracing()
    t0 = time.time()
    stats = run_all()
    print_table(stats)
    with open(os.path.join(out_dir, "BENCH_fig9_rodinia.json"), "w") as f:
        json.dump(results_doc(stats), f, indent=1)
    obs.write_chrome_trace(os.path.join(out_dir, "fig9_rodinia.trace.json"),
                           obs.tracer.drain())
    print(f"# artifacts: {out_dir}/BENCH_fig9_rodinia.json + "
          f"fig9_rodinia.trace.json")
    print(f"# fig9 wall time {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
