"""The DeepSeek-V2 family's controls at a small width on the CPU, each put
in the program's place on the same finished requests of one small
window: the reference in float8 (the step below bf16), the rotary without
YaRN, and the routed experts left out.  The bf16 program stays under the
small cell's limit and every control reads above it."""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import serving, small  # noqa: E402
from benchmarks.chip.small_deepseek import LIMITS, config  # noqa: E402


@pytest.fixture(scope="module")
def window():
    cfg = config()
    win, params, sched = serving.prepare(cfg, small.CLOSED, 2, 1.0,
                                         time.perf_counter())
    serving.run_window(win, small.CLOSED, sched, 1.0, 40)
    serving.free_engine(win)
    return cfg, params, win.tracker.all


@pytest.mark.parametrize("control", ["fp8", "plain_rope", "no_routed"])
def test_control_reads_over_the_limit_where_the_program_passes(control,
                                                               window):
    cfg, params, reqs = window
    program = serving.check(reqs, params, cfg, 2)["max_gap"]
    ctl = serving.check(reqs, params, cfg, 2, control=control)["max_gap"]
    assert program <= LIMITS["max_logit_gap"] < ctl, (program, ctl)
