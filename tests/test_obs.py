"""Telemetry layer: registry instruments, span nesting + Chrome-trace
round-trip, spans on the profiler's clock, PerfReport golden math,
kernel wrappers."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs.export import event_tree, load_chrome_trace, text_summary
from repro.obs.perf import PerfReport
from repro.obs.registry import Registry
from repro.obs.tracing import Tracer


# ---------------------------------------------------------------- registry

def test_registry_instruments():
    reg = Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    for i in range(100):
        reg.histogram("h").observe(i)
    snap = reg.snapshot()
    assert snap["c"]["value"] == 5
    assert snap["g"]["value"] == 2.5
    assert snap["h"]["count"] == 100
    assert snap["h"]["min"] == 0 and snap["h"]["max"] == 99
    assert abs(snap["h"]["mean"] - 49.5) < 1e-9
    assert 40 <= snap["h"]["p50"] <= 60
    # snapshot is JSON-serializable
    json.dumps(snap)


# ----------------------------------------------------------------- tracing

def test_span_disabled_is_noop_and_shared():
    tr = Tracer()
    a = tr.span("x")
    b = tr.span("y", k=1)
    assert a is b                      # shared no-op object: zero alloc
    with a:
        pass
    assert tr.events == []


@pytest.mark.parametrize("enabled", [False, True], ids=["off", "on"])
def test_span_on_profiler_clock(tmp_path, enabled):
    """Spans reach the host plane of a `jax.profiler` capture whether the
    tracer is on (and then also recorded) or off; off, with no capture
    running, a span is the shared no-op."""
    from benchmarks.chip import trace_reduce
    tr = Tracer()
    if enabled:
        tr.enable()
    else:
        assert tr.span("a") is tr.span("b")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("outer", k=1):
            with tr.span("inner"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    host = trace_reduce.load(str(tmp_path)).host
    spans = {e.name: e for evs in host.values() for e in evs}
    assert spans["outer"].start <= spans["inner"].start
    assert spans["inner"].end <= spans["outer"].end
    assert [e["name"] for e in tr.drain()] == (
        ["inner", "outer"] if enabled else [])
    if not enabled:
        assert tr.span("a") is tr.span("b")     # no-op again after capture


def test_span_nesting_and_export_round_trip(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("outer", rid=1):
        with tr.span("inner_a"):
            pass
        with tr.span("inner_b"):
            with tr.span("leaf"):
                pass
    path = str(tmp_path / "t.trace.json")
    obs.write_chrome_trace(path, tr.drain())

    loaded = load_chrome_trace(path)           # plain json.load under the hood
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)

    roots = event_tree(loaded)
    assert len(roots) == 1
    outer = roots[0]
    assert outer["name"] == "outer" and outer["args"] == {"rid": 1}
    kids = [c["name"] for c in outer["children"]]
    assert kids == ["inner_a", "inner_b"]
    grand = outer["children"][1]["children"]
    assert [g["name"] for g in grand] == ["leaf"]
    # the text summary mentions every span
    txt = text_summary(loaded)
    for name in ("outer", "inner_a", "inner_b", "leaf"):
        assert name in txt


def test_span_decorator_and_drain():
    tr = Tracer()
    tr.enable()

    @tr.span("work")
    def f(x):
        return x + 1

    assert f(1) == 2
    evs = tr.drain()
    assert [e["name"] for e in evs] == ["work"]
    assert tr.events == []
    assert evs[0]["ph"] == "X" and evs[0]["dur"] > 0


# -------------------------------------------------------------- PerfReport

def test_perf_report_golden():
    stats = {
        "cycles": 1000, "instrs": 800, "idle_cycles": 50,
        "stall_cycles": 150, "loads": 90, "stores": 10,
        "dcache_hits": 75, "dcache_misses": 25,
        "bank_conflict_cycles": 20, "divergent_splits": 4,
        "uniform_splits": 6, "joins": 10, "barrier_waits": 3,
        "divergence_violations": 0, "sched_refills": 12,
        "occupancy_cycles": 3000, "issued_lanes": 2400,
    }
    rep = PerfReport.from_stats(stats, warps=4, threads=4)
    assert rep.ipc == pytest.approx(0.8)
    assert rep.idle_frac == pytest.approx(0.05)
    assert rep.dcache_hit_rate == pytest.approx(0.75)
    assert rep.bank_conflict_rate == pytest.approx(0.2)
    assert rep.warp_occupancy == pytest.approx(3.0)
    assert rep.lane_utilization == pytest.approx(2400 / (800 * 4))
    assert rep.sched_refills == 12
    s = str(rep)
    assert "IPC" in s and "0.8000" in s and "75.0%" in s
    # round-trips to a plain dict (for BENCH_*.json artifacts)
    json.dumps(rep.as_dict())


def test_perf_report_empty_stats_no_division_by_zero():
    rep = PerfReport.from_stats({})
    assert rep.ipc == 0.0 and rep.dcache_hit_rate == 0.0
    str(rep)


def test_machine_perf_report_from_real_run():
    """Counters from an actual SIMT run produce a sane report."""
    from repro.core.simt import machine
    from repro.runtime.asm import assemble
    mc = machine.MachineConfig(warps=2, threads=2, max_cycles=10_000)
    st = machine.run(mc, assemble("""
    nt t0
    tmc t0
    tid t1
    slli t2, t1, 2
    li t3, 0x200
    add t2, t2, t3
    sw t1, 0(t2)
    lw t4, 0(t2)
    halt
"""))
    rep = machine.perf_report(st, mc)
    assert rep.instrs > 0 and 0 < rep.ipc <= 1.0
    assert 0 <= rep.warp_occupancy <= mc.warps
    assert 0 < rep.lane_utilization <= 1.0
    assert rep.loads == 1 and rep.stores == 1
    assert rep.sched_refills > 0


# ---------------------------------------------------------- kernel wrapper

def test_instrument_kernel_disabled_passthrough():
    reg = Registry()
    calls = []

    def fake_kernel(x):
        calls.append(1)
        return x * 2

    k = obs.instrument_kernel("fake", fake_kernel, registry=reg)
    obs.disable_kernel_timing()
    assert int(k(jnp.int32(3))) == 6
    assert reg.snapshot() == {}        # nothing recorded when disabled


def test_instrument_kernel_enabled_counts_and_times():
    reg = Registry()

    def fake_kernel(x):
        return x * 2

    k = obs.instrument_kernel("fake", fake_kernel, registry=reg)
    obs.enable_kernel_timing()
    try:
        assert int(k(jnp.int32(3))) == 6
        assert int(k(jnp.int32(4))) == 8
        snap = reg.snapshot()
        assert snap["kernels.fake.launches"]["value"] == 2
        assert snap["kernels.fake.time_s"]["count"] == 2

        # under an outer jit trace: launch counted, no timing recorded
        jitted = jax.jit(lambda x: k(x))
        assert int(jitted(jnp.int32(5))) == 10
        snap = reg.snapshot()
        assert snap["kernels.fake.launches"]["value"] == 3
        assert snap["kernels.fake.time_s"]["count"] == 2
    finally:
        obs.disable_kernel_timing()


# ------------------------------------------------------------- openmetrics

def test_openmetrics_golden_text():
    reg = Registry()
    reg.counter("serving.tokens").inc(42)
    reg.gauge("serving.queue_depth").set(3.0)
    reg.gauge("never.set")                       # unset gauge: skipped
    for v in (0.25, 0.25, 0.5, 1.0):     # binary-exact: stable sum repr
        reg.histogram("serving.ttft_s").observe(v)
    got = obs.to_openmetrics(reg)
    assert got == (
        "# TYPE serving_queue_depth gauge\n"
        "serving_queue_depth 3.0\n"
        "# TYPE serving_tokens counter\n"
        "serving_tokens_total 42\n"
        "# TYPE serving_ttft_s histogram\n"
        'serving_ttft_s_bucket{le="0.001"} 0\n'
        'serving_ttft_s_bucket{le="0.0025"} 0\n'
        'serving_ttft_s_bucket{le="0.005"} 0\n'
        'serving_ttft_s_bucket{le="0.01"} 0\n'
        'serving_ttft_s_bucket{le="0.025"} 0\n'
        'serving_ttft_s_bucket{le="0.05"} 0\n'
        'serving_ttft_s_bucket{le="0.1"} 0\n'
        'serving_ttft_s_bucket{le="0.25"} 2\n'
        'serving_ttft_s_bucket{le="0.5"} 3\n'
        'serving_ttft_s_bucket{le="1.0"} 4\n'
        'serving_ttft_s_bucket{le="2.5"} 4\n'
        'serving_ttft_s_bucket{le="5.0"} 4\n'
        'serving_ttft_s_bucket{le="10.0"} 4\n'
        'serving_ttft_s_bucket{le="+Inf"} 4\n'
        "serving_ttft_s_count 4\n"
        "serving_ttft_s_sum 2.0\n"
        "# EOF\n")
    # a snapshot dict renders identically to the live registry
    assert obs.to_openmetrics(reg.snapshot()) == got


def test_openmetrics_bucketless_snapshot_falls_back_to_summary():
    """Foreign / pre-bucket snapshot dicts still render (as summaries)."""
    snap = {"ttft": {"type": "histogram", "count": 2, "sum": 3.0,
                     "p50": 1.0, "p90": 2.0, "p99": 2.0}}
    text = obs.to_openmetrics(snap)
    assert "# TYPE ttft summary" in text
    assert 'ttft{quantile="0.5"} 1.0' in text
    assert text.endswith("# EOF\n")


def test_histogram_buckets_cumulative_and_custom():
    reg = Registry()
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0, 5.0):
        h.observe(v)
    s = reg.snapshot()["lat"]
    assert s["buckets"] == [[1.0, 1], [10.0, 3], ["+Inf", 4]]
    text = obs.to_openmetrics(reg)
    assert 'lat_bucket{le="1.0"} 1' in text
    assert 'lat_bucket{le="10.0"} 3' in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    # same name returns the same instrument (buckets pinned at creation)
    assert reg.histogram("lat") is h


def test_histogram_snapshot_concurrent_with_observe():
    """A scrape racing a writer thread must never tear: count == +Inf
    cumulative bucket count == reservoir-backed count in every snapshot."""
    import threading as _t
    reg = Registry()
    h = reg.histogram("h")
    stop = _t.Event()

    def writer():
        i = 0
        while not stop.is_set():
            h.observe(i * 0.001)
            i += 1

    th = _t.Thread(target=writer)
    th.start()
    try:
        for _ in range(200):
            s = reg.snapshot()["h"]
            if s["count"] == 0:
                continue
            assert s["buckets"][-1][1] == s["count"]
            assert s["count"] * s["mean"] == pytest.approx(s["sum"])
    finally:
        stop.set()
        th.join()


def test_openmetrics_name_sanitization_and_empty():
    reg = Registry()
    reg.counter("faults.injected.serving.logits.nan-logits").inc()
    text = obs.to_openmetrics(reg)
    assert "faults_injected_serving_logits_nan_logits_total 1" in text
    assert text.endswith("# EOF\n")
    assert obs.to_openmetrics(Registry()) == "# EOF\n"
