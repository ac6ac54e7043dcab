"""SIMT cells: the paper's cycle-level machine running the Rodinia subset
through the program's own entry, `repro.runtime.kernels_src.rodinia.
BENCHMARKS[name]` -> `core/simt/machine.run`, one launch after the
other, and every launch's output checked against the plain reference.

  set-up   each kernel launched once (its run loop compiles once per
           program length and miss latency).
  window   `seconds` of launches from the mix, closed loop; a launch that
           starts before the close completes and counts.  A traced run
           traces one pass of the mix with its costliest kernel (by the
           set-up's cycles) left out.
  check    every launch of the window: its output words in the
           machine's data memory against reference/<family>.py.
"""
from __future__ import annotations

import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.chip import bench, trace_reduce, traffic

MACHINE_KEYS = ("warps", "threads", "ipdom_depth", "barriers", "dmem_words",
                "smem_words", "cache_lines", "cache_ways", "cache_banks",
                "line_words", "miss_pipeline", "max_cycles")
# machine runs per call of a kernel (gaussian is Fan1 then Fan2)
RUNS = {"gaussian": 2}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def machine_config(cfg: Dict[str, Any], miss_latency: int):
    from repro.core.simt.machine import MachineConfig
    return MachineConfig(miss_latency=miss_latency,
                         **{k: cfg[k] for k in MACHINE_KEYS})


def launch(cfg, spec, annotate) -> Dict[str, Any]:
    """One kernel through the program's entry; the result's state stays
    on the device for the check."""
    from repro.runtime.kernels_src import rodinia
    mc = machine_config(cfg, spec["miss_latency"])
    with annotate("simt.launch"):
        res, _ok = rodinia.BENCHMARKS[spec["name"]](
            mc, seed=spec["data_seed"], **spec["kwargs"])
    return {"spec": spec, "state": res.state, "cycles": res.stats["cycles"],
            "instrs": res.stats["instrs"],
            "runs": RUNS.get(spec["name"], 1)}


def run_cell(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any],
             *, seed: int, seconds: float, trace: bool, t_process: float,
             meter, device: Dict[str, Any], spec_metrics: List[Dict],
             limits: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    import jax
    annotate = jax.profiler.TraceAnnotation
    limits = limits or bench.limits(cell["name"])
    # set-up: one launch of each kernel, on data of its own
    setup_cycles = {}
    for spec in traffic.launches(mix, seed ^ 0x5EED, 1):
        setup_cycles[spec["name"]] = launch(cfg, spec, annotate)["cycles"]
    costliest = max(setup_cycles, key=setup_cycles.get)
    setup_s = time.perf_counter() - t_process
    log(f"[setup] {setup_s:.3f} s (compiles so far {meter.compiles}, "
        f"{meter.compile_s:.3f} s; persistent-cache hits {meter.hits})")
    compiles0 = meter.compiles
    tracer = bench.TraceWindow(trace)
    stream = iter(traffic.launches(mix, seed, 100_000))
    done: List[Dict[str, Any]] = []
    t0 = now = time.perf_counter()
    t_end = t0 + seconds
    while now < t_end:
        spec = next(stream)
        # the trace holds one pass with the costliest kernel left out: it
        # opens after that kernel and closes before it comes again (every
        # simulated cycle records some hundred device operations)
        if tracer.on and spec["name"] == costliest:
            tracer.stop()
        elif (done and done[-1]["spec"]["name"] == costliest
              and tracer.lead_passed(now, t0, t_end)):
            tracer.start()
        traced = tracer.on
        rec = launch(cfg, spec, annotate)
        now = time.perf_counter()
        rec.update(t=now, traced=traced)
        done.append(rec)
    tracer.stop()
    window = now - t0
    compiles = meter.compiles - compiles0
    mem_peak = bench.memory_peak_bytes(device["count"])
    instrs = sum(L["instrs"] for L in done)
    cycles = sum(L["cycles"] for L in done)
    log(f"[window] {window:.3f} s; {len(done)} launches, {instrs} "
        f"instructions in {cycles} cycles; compiles inside the window: "
        f"{compiles}")
    walls = np.diff([t0] + [L["t"] for L in done])
    per = {}
    for L, w in zip(done, walls):
        per.setdefault(L["spec"]["name"], []).append(w)
    log("[window] median wall per launch: " + ", ".join(
        f"{k} {bench.quantile(v, .5) * 1e3:.3f} ms ({len(v)})"
        for k, v in per.items()))
    values = {"setup_s": setup_s, "sim_instrs_per_s": instrs / window}

    ctx = {"cfg": cfg, "mix": mix, "device": device, "window_s": window,
           "launches": done, "trace": None}
    breakdown, dev_extra = None, {}
    if trace and tracer.dir:
        tr = trace_reduce.load(tracer.dir)
        ctx["trace"] = tr
        dev_extra = {"busy_s": trace_reduce.busy_s(tr),
                     "window_s": trace_reduce.window_s(tr)}
        breakdown = trace_reduce.breakdown(tr)
        import shutil
        shutil.rmtree(tracer.dir, ignore_errors=True)
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in spec_metrics:
        v = (bench.metric_reader(m["name"])(ctx) if trace
             else values.get(m["name"]))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            log(f"[metric] {m['name']} = {v} {m['unit']}")

    ref = bench.load_module(f"reference/{cfg['family']}.py")
    bad_launches = bad_words = 0
    for L in done:
        s = L["spec"]
        n = ref.mismatches(s["name"], s["kwargs"], s["data_seed"],
                           np.asarray(L["state"].dmem))
        bad_words += n
        bad_launches += n > 0
    kinds = {L["spec"]["name"] for L in done}
    log(f"[check] {len(done)} launches of {len(kinds)} kernels; "
        f"{bad_launches} with output words off the reference "
        f"({bad_words} words)")
    compared = {
        "mismatched_launches": {"value": bad_launches,
                                "limit": int(limits["mismatched_launches"])},
        "kernels_checked": {"value": len(kinds),
                            "limit": int(limits["kernels_checked"])},
    }
    correct = (bad_launches <= limits["mismatched_launches"]
               and len(kinds) >= limits["kernels_checked"])
    return {"correct": correct, "attempted": len(done),
            "failed": bad_launches, "metrics": metrics,
            "device": dict(device, memory_peak_bytes=mem_peak, **dev_extra),
            "breakdown": breakdown, "compared": compared}
