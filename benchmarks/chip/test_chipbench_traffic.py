"""The traffic generator: seeds, stratified sizes, and the shapes the
serving cells rely on."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import bench, traffic  # noqa: E402

BIG_SEED = 2**33 + 17       # a run's seed may exceed 32 bits


def _mix(name):
    return bench.load_json(os.path.join(bench.HERE, "traffic",
                                        name + ".json"))


def _cfg(name):
    return bench.load_json(os.path.join(bench.HERE, "configs",
                                        name + ".json"))


@pytest.mark.parametrize("mix,cfg", [("decode_long.phi3", "phi3-mini-3.8b"),
                                     ("decode_long.danube",
                                      "h2o-danube-1.8b")])
def test_closed_seeded_and_stratified(mix, cfg):
    m, c = _mix(mix), _cfg(cfg)
    S = c["engine"]["n_slots"]
    n = S + 4 * m["clients"]

    def draw(seed):
        return traffic.closed_requests(m, seed, n, c["vocab_size"],
                                       c["max_len"], S)
    a, b, d = draw(BIG_SEED), draw(BIG_SEED), draw(5)
    assert a == b
    assert a != d
    for reqs in (a, d):
        for r in reqs:
            assert len(r["prompt"]) + r["max_new"] <= c["max_len"] - 1
            assert m["prompt_len"]["lo"] <= len(r["prompt"]) \
                <= m["prompt_len"]["hi"]
    # the slots' residual answers, then every block of `clients`
    # requests, hold the same sizes whatever the seed: only their order
    # differs
    k = m["clients"]
    for lo, hi in [(0, S)] + [(i, i + k) for i in range(S, n, k)]:
        sizes = [sorted((len(r["prompt"]), r["max_new"]) for r in reqs[lo:hi])
                 for reqs in (a, d)]
        assert sorted(s[0] for s in sizes[0]) == \
            sorted(s[0] for s in sizes[1])
        assert sorted(s[1] for s in sizes[0]) == \
            sorted(s[1] for s in sizes[1])


def test_residual_is_the_stationary_remainder():
    """Uniform lengths 1..4: P(L >= r) is 4, 3, 2, 1 quarters, so the
    remainder of a request found in service is 1..4 with weights
    4:3:2:1."""
    r = traffic.residual({"dist": "uniform_int", "lo": 1, "hi": 4}, 10,
                         traffic._rng(1))
    assert sorted(r) == [1] * 4 + [2] * 3 + [3] * 2 + [4]
    # a long-tailed answer length: the remainder's mean is E[L^2] / 2E[L]
    dist = {"dist": "lognormal", "median": 320, "sigma": 0.6, "cap": 880}
    L = traffic.quantiles(dist, 4096, integer=True)
    mean_l = sum(L) / len(L)
    want = sum(x * (x + 1) for x in L) / (2 * sum(L))
    got = traffic.residual(dist, 400, traffic._rng(2))
    assert sum(got) / len(got) == pytest.approx(want, rel=0.02)
    assert want < mean_l


def _session_prompts(sched, answer=lambda n: [7] * n):
    """Every turn's prompt, as the harness builds it (answers stand in
    for the served tokens: max_new + 1 of them)."""
    out = []
    for s in sched["sessions"]:
        hist = sched["system"]
        prompts = []
        for t in s["turns"]:
            p = hist + t["user"]
            prompts.append((p, t["max_new"]))
            hist = p + answer(t["max_new"] + 1)
        out.append(prompts)
    return out


def test_sessions_seeded_and_fit():
    m, c = _mix("chat_sessions"), _cfg("phi3-mini-3.8b")
    a = traffic.sessions(m, BIG_SEED, 40, c["vocab_size"], c["max_len"])
    b = traffic.sessions(m, BIG_SEED, 40, c["vocab_size"], c["max_len"])
    d = traffic.sessions(m, 6, 40, c["vocab_size"], c["max_len"])
    assert a == b and a != d
    assert len(a["sessions"]) == round(m["rate_per_s"] * 40)
    # the same arrival gaps in another order: the last start is the same
    assert a["sessions"][-1]["start_s"] == pytest.approx(
        d["sessions"][-1]["start_s"])
    for sched in (a, d):
        for prompts in _session_prompts(sched):
            for p, max_new in prompts:
                assert len(p) + max_new <= c["max_len"] - 1


def test_sessions_share_prefixes_at_chunk_granularity():
    """Turn 1 starts with the shared system prompt; turn k+1 starts with
    turn k's whole prompt and answer, so the prefix cache can hit at
    least the full chunks of the previous prompt."""
    from repro.serving.prefix_cache import chain_hashes
    m, c = _mix("chat_sessions"), _cfg("phi3-mini-3.8b")
    C = c["engine"]["prefill_chunk"]
    sched = traffic.sessions(m, BIG_SEED, 40, c["vocab_size"], c["max_len"])
    system = sched["system"]
    sys_chunks = chain_hashes(system + [0], C)
    assert len(sys_chunks) == len(system) // C == 8
    multi = 0
    for prompts in _session_prompts(sched):
        assert prompts[0][0][:len(system)] == system
        assert chain_hashes(prompts[0][0], C)[:8] == sys_chunks
        for (p, _), (q, _) in zip(prompts, prompts[1:]):
            assert q[:len(p)] == p
            shared = chain_hashes(p, C)
            assert chain_hashes(q, C)[:len(shared)] == shared
            assert len(shared) >= 8
            multi += 1
    assert multi > 0


def test_launches_seeded():
    mix = {"kernels": [{"name": "vecadd", "kwargs": {"n": 256},
                        "miss_latency": 16},
                       {"name": "bfs", "kwargs": {"n_nodes": 512,
                                                  "avg_deg": 4},
                        "miss_latency": 200}]}
    a = traffic.launches(mix, BIG_SEED, 3)
    assert a == traffic.launches(mix, BIG_SEED, 3)
    assert a != traffic.launches(mix, 1, 3)
    assert [x["name"] for x in a] == ["vecadd", "bfs"] * 3
    assert len({x["data_seed"] for x in a}) == 6


def test_gamma_arrivals_keep_the_mean():
    g = traffic.stratified({"dist": "gamma", "mean": 2.0, "cv": 2.0}, 400,
                           traffic._rng(1))
    assert sum(g) / len(g) == pytest.approx(2.0, rel=0.05)
