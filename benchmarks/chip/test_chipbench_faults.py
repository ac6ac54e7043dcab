"""A whole serving run at a small width on the CPU (the harness's look
for a chip skipped), clean and with the timed path broken underneath:
`correct` must come out false for each fault a serving cell can have,
in a closed loop and in an open loop of sessions busy enough that every
slot serves a request (its sample holds every slot that finished one,
and a fault in a lane shows only where that lane serves).

- a token altered where it is produced: the decode step's sampled
  tokens shifted by one id;
- a token altered in one lane only: the last slot's sampled token
  shifted by one id, which only a sample across slots sees;
- a step that returns its state unchanged: the decode step hands back
  the KV cache it was given, so no decoded token is ever written.

(Half of a batch left out of a mean, and the exchange between chips,
have no place in a one-chip serving cell.)"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import serving, small  # noqa: E402

MIXES = {"closed": small.CLOSED, "sessions": small.SESSIONS,
         "sessions_busy": dict(small.SESSIONS, rate_per_s=12.0)}


def _alter_tokens(eng):
    step, V = eng._decode_fn, eng.cfg.vocab_size

    def altered(*a):
        out = step(*a)
        return ((out[0] + 1) % V,) + tuple(out[1:])
    eng._decode_fn = altered


def _alter_last_lane(eng):
    step, V = eng._decode_fn, eng.cfg.vocab_size

    def altered(*a):
        out = step(*a)
        return (out[0].at[-1].set((out[0][-1] + 1) % V),) + tuple(out[1:])
    eng._decode_fn = altered


def _keep_state(eng):
    import jax
    import jax.numpy as jnp
    step = eng._decode_fn

    def unchanged(params, caches, *rest):
        kept = jax.tree.map(jnp.copy, caches)
        out = step(params, caches, *rest)
        return (out[0], kept) + tuple(out[2:])
    eng._decode_fn = unchanged


@pytest.mark.parametrize("mix", list(MIXES))
def test_clean_run_is_correct(mix):
    res = small.run(MIXES[mix])
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    names = set(res["metrics"])
    assert {"itl_p95_ms", "tokens_per_s", "setup_s"} <= names
    assert ("ttft_p95_ms" in names) == (mix != "closed")
    if mix != "sessions":
        assert res["compared"]["checked_slots"]["value"] == 4
    assert list(res)[-1] == "compared"


FAULTS = {"token_altered": _alter_tokens,
          "one_lane_altered": _alter_last_lane,
          "state_unchanged": _keep_state}


@pytest.mark.parametrize(
    "mix,fault",
    [("closed", f) for f in FAULTS] + [("sessions_busy", f) for f in FAULTS],
    ids=list(FAULTS) + [f"sessions-{f}" for f in FAULTS])
def test_broken_step_is_not_correct(mix, fault, monkeypatch):
    build = serving.build_engine

    def broken(*args):
        eng = build(*args)
        FAULTS[fault](eng)
        return eng
    monkeypatch.setattr(serving, "build_engine", broken)
    res = small.run(MIXES[mix])
    assert not res["correct"], res["compared"]
    assert res["compared"]["checked_slots"]["value"] == 4
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
