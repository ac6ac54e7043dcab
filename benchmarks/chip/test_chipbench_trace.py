"""trace_reduce on a synthetic TPU-shaped trace (built as an XSpace text
proto, read back through jax.profiler.ProfileData) and on a trace the
profiler records here on the CPU."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import trace_reduce as tr  # noqa: E402

US = 10_000_000     # picoseconds in one time unit of the events below


def _xspace(device_ops, modules, host):
    """Text proto of one device plane (ops and module lines) and one host
    thread; each event (name, start, duration) in units of 10 us."""
    names = sorted({n for n, _, _ in device_ops + modules})
    ids = {n: i + 1 for i, n in enumerate(names)}

    def events(evs, ids):
        return "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * US} "
            f"duration_ps: {d * US} }}\n" for n, s, d in evs)

    def meta(ids):
        return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in ids.items())
    hnames = sorted({n for n, _, _ in host})
    hids = {n: i + 1 for i, n in enumerate(hnames)}
    return (f'planes {{ id: 1 name: "/device:TPU:0"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
            f'{events(device_ops, ids)}}}\n'
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n'
            f'{events(modules, ids)}}}\n{meta(ids)}}}\n'
            f'planes {{ id: 2 name: "/host:CPU"\n'
            f'lines {{ id: 3 name: "main" timestamp_ns: 0\n'
            f'{events(host, hids)}}}\n{meta(hids)}}}\n')


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    # window 0..100 units; two decode runs (10..30, 50..60), one chunk run
    # (70..90) overlapping its ops; host: window, two engine.step spans
    ops = [("%fusion.1 = bf16[8,128]{1,0} fusion(x)", 10, 15),
           ("%copy.2 = bf16[8,128]{1,0} copy(x)", 20, 10),   # overlaps
           ("%fusion.1 = bf16[8,128]{1,0} fusion(x)", 50, 10),
           ("%dot.3 = f32[4,4]{1,0} dot(a, b)", 70, 20),
           ("%fusion.1 = bf16[8,128]{1,0} fusion(x)", 120, 5)]  # outside
    mods = [("jit__decode_step(123)", 10, 20), ("jit__decode_step(123)", 50, 10),
            ("jit__prefill_chunk_step(9)", 70, 20),
            ("jit__decode_step(123)", 120, 5)]
    host = [("window", 0, 100), ("engine.step", 5, 40),
            ("engine.step", 48, 45), ("submit", 45, 2)]
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(_xspace(ops, mods, host)))
    return tr.from_profile(pd)


def test_window_and_busy(trace):
    assert trace.window() == (0.0, 1_000_000.0)
    # union of ops in the window: 10..30, 50..60, 70..90 = 50 units
    assert tr.busy_s(trace) == pytest.approx(50e-6 * 10)
    assert tr.window_s(trace) == pytest.approx(100e-6 * 10)
    assert tr.idle_share(trace) == pytest.approx(0.5)


def test_program_time_counts_runs_inside_the_window(trace):
    assert tr.program_time(trace, "_decode_step") == (
        pytest.approx(30e-6 * 10), 2)
    assert tr.program_time(trace, "_prefill_chunk_step") == (
        pytest.approx(20e-6 * 10), 1)
    assert tr.program_time(trace, "_decode") == (0.0, 0)


def test_top_ops_by_name_and_result_type(trace):
    top = dict(tr.top_ops(trace))
    assert top["fusion.1 bf16[8,128]"] == pytest.approx(25e-6 * 10)
    assert top["dot.3 f32[4,4]"] == pytest.approx(20e-6 * 10)
    assert top["copy.2 bf16[8,128]"] == pytest.approx(10e-6 * 10)


def test_idle_gaps_labelled_by_host_span(trace):
    gaps = dict(tr.idle_gaps(trace))
    # 0..10 (engine.step from 5: midpoint 5 -> engine.step), 30..50
    # (midpoint 40 -> engine.step), 60..70 (65 -> engine.step),
    # 90..100 (95 -> nothing open)
    assert sum(gaps.values()) == pytest.approx(50e-6 * 10)
    assert gaps["engine.step"] == pytest.approx(40e-6 * 10)
    assert gaps["host idle"] == pytest.approx(10e-6 * 10)
    bd = tr.breakdown(trace)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_short_gaps_pooled():
    t = tr.Trace(ops={"/device:TPU:0": [tr.Event("a", 0, 100),
                                        tr.Event("b", 110, 200)]},
                 modules={}, host={"main": [tr.Event("window", 0, 200)]})
    assert dict(tr.idle_gaps(t)) == {tr.SHORT_GAP: pytest.approx(1e-8)}


def test_no_window_span_is_an_error():
    t = tr.Trace(ops={}, modules={}, host={"main": [tr.Event("x", 0, 1)]})
    with pytest.raises(ValueError):
        t.window()


def test_recorded_cpu_trace(tmp_path):
    """A real profiler trace: the harness's host spans are found, and the
    CPU has no device plane, so the idle share reads nothing."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = {e.name for evs in t.host.values() for e in evs}
    assert {"window", "engine.step"} <= names
    assert tr.window_s(t) > 0
    assert tr.device_planes(t) == []
    assert tr.idle_share(t) is None


def test_programs_stand_in_where_no_ops_were_recorded():
    t = tr.Trace(ops={}, modules={"/device:TPU:0": [
        tr.Event("jit__run_jit(1)", 0, 60_000)]},
        host={"main": [tr.Event("window", 0, 100_000)]})
    assert tr.busy_s(t) == pytest.approx(60e-6)
    assert tr.idle_share(t) == pytest.approx(0.4)
