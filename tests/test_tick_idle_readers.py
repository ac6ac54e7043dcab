"""The per-tick device idle readers (`tick_idle_host_ms.decode`,
`tick_idle_readback_ms.decode`) on synthetic `trace_reduce.Trace`s."""
import pytest

from benchmarks.chip import bench
from benchmarks.chip import trace_reduce as tr

MS = 1_000_000          # ns in one time unit of the events below
READERS = ["tick_idle_host_ms.decode", "tick_idle_readback_ms.decode"]


def _ev(name, s, t):
    return tr.Event(name, s * MS, t * MS)


def _trace(ticks=True, device=True):
    """Window 0..100; tick 10..40 with a readback 30..38, tick 50..90
    (its step number in the name) with a readback 80..88, a tick outside
    the window, and a readback between ticks.  Device ops 12..32,
    36..45, 55..80 and 85..95: idle 10..12 and 50..55 is host code
    (7 units over 2 ticks), 32..36 and 80..85 readback (9 units); the
    gap 45..50 between ticks counts for neither."""
    host = [_ev("window", 0, 100), _ev("engine.step", 9, 41),
            _ev("x.readback", 46, 48), _ev("engine.step", 49, 91)]
    if ticks:
        host += [_ev("serving.tick", 10, 40),
                 _ev("serving.decode.readback", 30, 38),
                 _ev("serving.tick#step_num=1,_r=1#", 50, 90),
                 _ev("serving.prefill.readback", 80, 88),
                 _ev("serving.tick", 110, 120)]
    ops = [_ev("%fusion.1", s, t)
           for s, t in ((12, 32), (36, 45), (55, 80), (85, 95))]
    return tr.Trace(ops={"/device:TPU:0": ops} if device else {},
                    modules={}, host={"main": host})


@pytest.mark.parametrize("name,want", zip(READERS, [3.5, 4.5]))
def test_reader_splits_idle_per_tick(name, want):
    assert bench.metric_reader(name)({"trace": _trace()}) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("missing", ["ticks", "device", "trace"])
def test_reader_reads_nothing_without_ticks_or_device(name, missing):
    trace = (None if missing == "trace" else
             _trace(ticks=missing != "ticks", device=missing != "device"))
    assert bench.metric_reader(name)({"trace": trace}) is None
