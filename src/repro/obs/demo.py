"""End-to-end observability demo.

    PYTHONPATH=src python -m repro.obs.demo [--out obs_demo.trace.json]

Runs (1) SIMT Rodinia kernels on the cycle-level machine and prints a
Vortex-style PerfReport PER KERNEL LAUNCH (the gaussian pipeline shows
two: fan1 and fan2), (2) a short serving session on a reduced model —
with the live HTTP plane up, scraping its own `/metrics` + `/healthz`
and printing the serving snapshot, (3) dumps and schema-validates a
flight-recorder artifact, then (4) writes a Chrome trace-event JSON of
everything (per-request Perfetto tracks included) and verifies it
round-trips through `json.load`.  Load the trace at
https://ui.perfetto.dev.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import urllib.request

from repro import obs
from repro.obs.flight import flight, validate_flight


def run_simt_section() -> None:
    from repro.core.simt import machine
    from repro.core.simt.machine import launch_log
    from repro.runtime.kernels_src import rodinia

    mc = machine.MachineConfig(warps=4, threads=4, miss_latency=16)
    launch_log.enable()
    with obs.trace.span("simt:saxpy", warps=mc.warps, threads=mc.threads):
        res, ok = rodinia.BENCHMARKS["saxpy"](mc, n=128, repeats=4)
    assert ok, "saxpy verification failed"
    res2, ok2 = rodinia.BENCHMARKS["gaussian"](mc, n=12)
    assert ok2, "gaussian verification failed"
    # per-kernel PerfReports: one per launch label, not one per run —
    # gaussian's two-kernel pipeline gets separate fan1/fan2 reports
    for label, rep in launch_log.reports(mc).items():
        print(f"[{label}]")
        print(rep)
        assert rep.ipc > 0, f"empty PerfReport for {label}"
    rep = machine.perf_report(res.stats, mc)
    assert rep.ipc > 0 and rep.dcache_hit_rate > 0, "empty PerfReport"
    obs.metrics.gauge("simt.ipc").set(rep.ipc)
    obs.metrics.gauge("simt.dcache_hit_rate").set(rep.dcache_hit_rate)
    launch_log.disable()


def run_serving_section() -> None:
    import jax
    from repro.configs import reduced_config
    from repro.models import api
    from repro.serving.engine import Engine

    cfg = reduced_config("phi3-mini-3.8b").replace(num_layers=2)
    params = api.build_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, n_slots=4, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    # the live HTTP plane: scrape our own endpoints mid-demo
    with obs.ObsServer(port=0, registries=[eng.metrics, obs.metrics],
                       health=eng.liveness, requests=eng.debug_requests,
                       flight=flight) as srv:
        t0 = time.perf_counter()
        with obs.trace.span("serve_session"):
            for p in ([5, 9, 2], [7, 1], [3, 3, 3, 3], [11, 4]):
                eng.submit(p, max_new=6)
            eng.run()
        # a rate is the `serving.tokens` counter over the caller's own
        # clock (here the session's, compiles included)
        session_s = time.perf_counter() - t0
        eng.liveness.done()
        base = f"http://127.0.0.1:{srv.port}"
        om = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert om.endswith("# EOF\n") and '_bucket{le="' in om
        hz = json.load(urllib.request.urlopen(f"{base}/healthz"))
        dr = json.load(urllib.request.urlopen(f"{base}/debug/requests"))
        print(f"live plane: {base}  ({len(om.splitlines())} OpenMetrics "
              f"lines, healthz={hz['state']}, {len(dr)} request rows)")
    snap = eng.metrics_snapshot()
    ttft = snap["serving.ttft_s"]
    print("serving metrics:")
    print(f"  requests        {snap['serving.requests_completed']['value']}"
          f" completed ({snap['serving.requests_completed.max_new']['value']}"
          f" by max_new)")
    print(f"  TTFT            mean {ttft['mean']*1e3:.1f} ms  "
          f"p99 {ttft['p99']*1e3:.1f} ms  (n={ttft['count']})")
    print(f"  inter-token     mean {snap['serving.itl_s']['mean']*1e3:.1f} ms")
    tokens = snap["serving.tokens"]["value"]
    print(f"  tokens          {tokens}  ({tokens / session_s:.1f} tok/s "
          "over the session)")
    print(f"  batch efficiency "
          f"{snap['serving.decode_lanes_selected']['value']}"
          f"/{snap['serving.decode_lanes_total']['value']} lanes")
    assert ttft["count"] > 0 and snap["serving.tokens"]["value"] > 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="obs_demo.trace.json")
    args = ap.parse_args(argv)

    obs.enable_tracing()
    obs.enable_kernel_timing()
    flight.enable()
    flight.attach_tracer(obs.tracer)
    flight.add_metrics_source(obs.metrics)

    print("---- SIMT machine ----")
    run_simt_section()
    print("\n---- serving ----")
    run_serving_section()

    print("\n---- flight recorder ----")
    with tempfile.TemporaryDirectory() as td:
        path = flight.dump(td, reason="demo")
        doc = json.load(open(path))
        validate_flight(doc)
        kinds = sorted({e["kind"] for e in doc["events"]})
        print(f"flight dump: {doc['n_events']} events "
              f"({doc['dropped']} dropped), kinds={kinds}")
        assert "serving.finish" in kinds and "simt.launch" in kinds

    events = obs.tracer.drain()
    obs.write_chrome_trace(args.out, events,
                           metadata={"demo": "repro.obs"})
    loaded = obs.load_chrome_trace(args.out)          # json.load round-trip
    names = {e["name"] for e in loaded if e.get("ph") == "X"}
    assert len(names) >= 3, f"expected >=3 span names, got {names}"
    print("\n---- trace ----")
    print(obs.text_summary(loaded))
    print(f"\nwrote {args.out} ({len(loaded)} events, "
          f"{len(names)} span names) — load it at https://ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
