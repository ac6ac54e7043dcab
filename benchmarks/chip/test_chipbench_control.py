"""The serving cells' control at a small width: the plain reference in
float8 put in the program's place.  On the same finished requests, the
bf16 program stays under the small cells' limit and the fp8 control
reads above it, on three seeds, in a closed loop and in an open loop of
sessions (the chip readings are in PERF.md)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import small  # noqa: E402


@pytest.mark.parametrize(
    "mix,seed", [(m, s) for m in ("closed", "sessions")
                 for s in (1, 2, 2**33 + 3)],
    ids=["1", "2", "8589934595", "sessions-1", "sessions-2",
         "sessions-8589934595"])
def test_control_fails_where_the_program_passes(mix, seed):
    program, control = small.readings(
        small.CLOSED if mix == "closed" else small.SESSIONS, seed)
    limit = small.SMALL_LIMITS["max_logit_gap"]
    assert program <= limit < control, (program, control)
