"""The serving cells' control at a small width: the plain reference in
float8 put in the program's place.  On the same finished requests, the
bf16 program stays under the small cells' limit and the fp8 control
reads above it, on three seeds (the chip readings are in PERF.md)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import small  # noqa: E402


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 3])
def test_control_fails_where_the_program_passes(seed):
    program, control = small.readings(small.CLOSED, seed)
    limit = small.SMALL_LIMITS["max_logit_gap"]
    assert program <= limit < control, (program, control)
