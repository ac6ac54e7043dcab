"""Serving engine: continuous batching == sequential decoding, slot
recycling, scheduler fairness."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.serving.engine import Engine
from repro.serving.scheduler import RequestScheduler

CFG = reduced_config("phi3-mini-3.8b").replace(num_layers=2)
PARAMS = api.build_params(jax.random.PRNGKey(0), CFG)
# float32 copy: random bf16 weights tie top logits, so argmax differs by path
CFG32 = CFG.replace(dtype="float32")
PARAMS32 = api.build_params(jax.random.PRNGKey(0), CFG32)


def ref_decode(prompt, n, max_len=64, cfg=CFG, params=PARAMS):
    lg, _, c = api.forward(params, {"tokens": jnp.asarray([prompt],
                                                          jnp.int32)},
                           cfg, mode="prefill", remat="none")
    c = api.grow_caches(cfg, c, max_len)
    out = [int(jnp.argmax(lg[0, -1, :cfg.vocab_size]))]
    for _ in range(n - 1):
        lg, _, c = api.forward(params, {"tokens": jnp.asarray([[out[-1]]],
                                                              jnp.int32)},
                               cfg, mode="decode", caches=c, remat="none")
        out.append(int(jnp.argmax(lg[0, -1, :cfg.vocab_size])))
    return out


def test_engine_matches_sequential_reference():
    eng = Engine(CFG32, PARAMS32, n_slots=4, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    prompts = [[5, 9, 2], [7, 1], [3, 3, 3, 3], [11, 4, 6], [8], [2, 9]]
    rids = [eng.submit(p, max_new=5) for p in prompts]
    eng.run()
    res = eng.results()
    # max_new counts decode tokens; prefill contributes one more
    for rid, p in zip(rids, prompts):
        assert res[rid] == ref_decode(p, 6, cfg=CFG32, params=PARAMS32), \
            (rid, p)


def test_slot_recycling_more_requests_than_slots():
    eng = Engine(CFG, PARAMS, n_slots=2, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    prompts = [[i + 1, i + 2] for i in range(5)]
    rids = [eng.submit(p, max_new=3) for p in prompts]
    eng.run()
    res = eng.results()
    for rid, p in zip(rids, prompts):
        assert res[rid] == ref_decode(p, 4), (rid, p)


def test_max_new_contract_and_finish_reason():
    """`max_new` = decode tokens after prefill, so a request that never
    hits EOS finishes with max_new + 1 output tokens, and the completion
    counters record the finish reason."""
    eng = Engine(CFG, PARAMS, n_slots=2, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    rid = eng.submit([5, 9, 2], max_new=4)
    eng.run()
    req = eng.requests[rid]
    assert len(req.out) == 5
    assert req.finish_reason == "max_new"
    snap = eng.metrics_snapshot()
    assert snap["serving.requests_completed"]["value"] == 1
    assert snap["serving.requests_completed.max_new"]["value"] == 1
    assert snap["serving.ttft_s"]["count"] == 1
    assert snap["serving.itl_s"]["count"] == 4
    assert snap["serving.tokens"]["value"] == 5


def test_run_raises_when_ticks_run_out():
    """run() never returns quietly with work left: out of ticks with a
    request still decoding (or queued) is an error."""
    eng = Engine(CFG, PARAMS, n_slots=1, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    eng.submit([5, 9, 2], max_new=4)
    eng.submit([7, 1], max_new=4)
    with pytest.raises(RuntimeError, match="1 requests queued and 1 in"):
        eng.run(max_ticks=2)
    eng.run()                             # the same engine then finishes
    assert all(r.finish_reason == "max_new" for r in eng.requests.values())


def test_results_before_any_admission():
    """_slot_req is initialized in __init__, so results()/step() on an
    engine that never admitted anything cannot raise AttributeError."""
    eng = Engine(CFG, PARAMS, n_slots=2, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    assert eng._slot_req == {}
    assert eng.results() == {}
    assert eng.step() == 0


def test_scheduler_no_duplicate_issue_per_tick():
    s = RequestScheduler(4)
    a = s.admit()
    b = s.admit()
    s.prefill_done(a)
    s.prefill_done(b)
    picked = s.next_batch(8)          # width > schedulable count
    assert sorted(picked) == sorted(set(picked))
    assert set(picked) <= {a, b}


def test_scheduler_round_robin_fairness():
    s = RequestScheduler(3)
    slots = [s.admit() for _ in range(3)]
    for x in slots:
        s.prefill_done(x)
    t1 = s.next_batch(2)
    t2 = s.next_batch(2)
    # the slot skipped in tick 1 must appear in tick 2 (visible-window)
    assert (set(slots) - set(t1)) <= set(t2)


def test_stalled_slots_not_decoded():
    s = RequestScheduler(2)
    a = s.admit()          # stays stalled (no prefill_done)
    b = s.admit()
    s.prefill_done(b)
    assert s.next_batch(2) == [b]


def test_chunked_and_legacy_prefill_agree():
    """Multi-chunk prompts through the chunked path produce exactly the
    greedy tokens the legacy bucketed prefill (and the sequential
    reference) produce."""
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], [4, 4, 2, 1],
               [9] * 20]
    outs = {}
    for mode in ("chunked", "legacy"):
        eng = Engine(CFG, PARAMS, n_slots=2, max_len=64, prompt_bucket=8,
                     prefill_chunk=8, prefill_mode=mode, eos_id=-1)
        rids = [eng.submit(p, max_new=4) for p in prompts]
        eng.run()
        outs[mode] = [eng.results()[r] for r in rids]
    assert outs["chunked"] == outs["legacy"]
    for out, p in zip(outs["chunked"], prompts):
        assert out == ref_decode(p, 5), p


def test_prefix_cache_hits_preserve_outputs():
    """Requests whose prompts share a cached prefix skip those chunk
    forwards entirely — and still emit exactly the reference tokens."""
    shared = list(range(1, 17))                # 16 tokens = 2 chunks of 8
    tails = [[21, 22, 23], [31, 32], [41]]
    eng = Engine(CFG, PARAMS, n_slots=1, max_len=64, prompt_bucket=8,
                 prefill_chunk=8, prefill_mode="chunked",
                 prefix_cache_entries=4, eos_id=-1)
    rids = [eng.submit(shared + t, max_new=3) for t in tails]
    eng.run()
    snap = eng.metrics_snapshot()
    assert snap["serving.prefix_cache.hits"]["value"] == 4   # 2 x 2 chunks
    assert snap["serving.prefix_cache.hit_tokens"]["value"] == 32
    assert snap["serving.prefix_cache.inserts"]["value"] >= 1
    for rid, t in zip(rids, tails):
        assert eng.results()[rid] == ref_decode(shared + t, 4), t


def test_finish_clears_slot_bookkeeping():
    """Retired requests leave no engine-side pins (slot map, prefill
    cursor, chunk hashes) — recycled slots start clean."""
    eng = Engine(CFG, PARAMS, n_slots=2, max_len=64, prompt_bucket=8,
                 eos_id=-1)
    for i in range(3):
        eng.submit([i + 1, i + 2, i + 3], max_new=2)
    eng.run()
    assert eng._slot_req == {}
    assert eng._prefill_pos == {}
    assert eng._chunk_hashes == {}


def test_scheduler_admit_when_pool_full():
    s = RequestScheduler(2)
    assert s.admit() == 0
    assert s.admit() == 1
    assert s.admit() == -1                     # pool full
    s.retire(0)
    assert s.admit() == 0                      # freed slot is reusable


def test_scheduler_barrier_excludes_prefill_and_decode():
    s = RequestScheduler(3)
    a, b, c = s.admit(), s.admit(), s.admit()
    s.barrier[b] = True                        # parked mid-prefill
    assert list(s.prefill_targets()) == [a, c]
    s.prefill_done(a)
    s.prefill_done(c)
    s.barrier[c] = True                        # parked after prefill
    assert s.next_batch(3) == [a]


def test_scheduler_retire_mid_window_refill():
    """A slot retired after issuing is never issued again, and the
    remaining window drains without a bubble."""
    s = RequestScheduler(3)
    slots = [s.admit() for _ in range(3)]
    for x in slots:
        s.prefill_done(x)
    first = s.next_batch(1)
    s.retire(first[0])
    seen = set()
    for _ in range(4):
        seen |= set(s.next_batch(1))
    assert first[0] not in seen
    assert seen == set(slots) - set(first)
    assert s.prefill_progress[first[0]] == 0   # progress cleared too


def test_scheduler_round_robin_over_many_ticks():
    """Two-level scheduling gives every slot the same issue share over a
    long horizon (the hierarchical warp-fairness property)."""
    s = RequestScheduler(4)
    slots = [s.admit() for _ in range(4)]
    for x in slots:
        s.prefill_done(x)
    counts = {x: 0 for x in slots}
    for _ in range(40):
        for w in s.next_batch(2):
            counts[w] += 1
    assert all(counts[x] == 20 for x in slots), counts


def test_step_masks_np_matches_hw_reference():
    """The serving scheduler's NumPy mask algebra is bit-exact with the
    cycle-level simulator's jnp version across random mask states."""
    import numpy as np

    from repro.serving.scheduler import step_masks_np
    from repro.core.simt import scheduler as hw
    rng = np.random.default_rng(0)
    for _ in range(200):
        W = int(rng.integers(1, 9))
        vis, act, st, bar = (rng.random(W) < 0.5 for _ in range(4))
        wid_np, vis_np = step_masks_np(vis, act, st, bar)
        wid_hw, vis_hw = hw.step_masks(jnp.asarray(vis), jnp.asarray(act),
                                       jnp.asarray(st), jnp.asarray(bar))
        assert wid_np == int(wid_hw)
        assert (vis_np == np.asarray(vis_hw)).all()


# ---------------------------------------------------------------------------
# paged KV layout: bit-identity with contiguous + COW/admission behavior
# ---------------------------------------------------------------------------


def _run_workload(prompts, max_new, *, layout, page_size=8, n_slots=2,
                  prefix_entries=0, kv_pages=None, cfg=CFG, params=PARAMS):
    eng = Engine(cfg, params, n_slots=n_slots, max_len=64, prompt_bucket=8,
                 prefill_chunk=8, prefill_mode="chunked", eos_id=-1,
                 prefix_cache_entries=prefix_entries, kv_layout=layout,
                 kv_page_size=page_size, kv_pages=kv_pages)
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run()
    res = eng.results()
    return ([res[r] for r in rids],
            [eng.requests[r].finish_reason for r in rids], eng)


def test_paged_bit_identical_to_contiguous():
    """The gate the issue demands: on the existing serving contract
    workloads, --kv-layout paged produces exactly the greedy tokens and
    finish reasons of the contiguous layout (which itself matches the
    sequential reference)."""
    workloads = [
        ([[5, 9, 2], [7, 1], [3, 3, 3, 3], [11, 4, 6], [8], [2, 9]], 5, 0),
        ([[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], [4, 4, 2, 1], [9] * 20],
         4, 0),
        ([list(range(1, 17)) + t for t in ([21, 22, 23], [31, 32], [41])],
         3, 4),                      # shared 16-token prefix, cache on
    ]
    for prompts, max_new, entries in workloads:
        toks_c, fin_c, _ = _run_workload(prompts, max_new, layout="contiguous",
                                         prefix_entries=entries, cfg=CFG32,
                                         params=PARAMS32)
        toks_p, fin_p, eng = _run_workload(prompts, max_new, layout="paged",
                                           prefix_entries=entries, cfg=CFG32,
                                           params=PARAMS32)
        assert toks_p == toks_c, prompts
        assert fin_p == fin_c, prompts
        for out, p in zip(toks_p, prompts):
            assert out == ref_decode(p, max_new + 1, cfg=CFG32,
                                     params=PARAMS32), p
    # the shared-prefix workload ran last: hits pinned pages instead of
    # copying (16-token prefix, page size 8 -> page-aligned, zero copies)
    snap = eng.metrics_snapshot()
    assert snap["serving.kv.pages_shared"]["value"] > 0
    assert snap.get("serving.kv.pages_copied", {"value": 0})["value"] == 0
    assert snap.get("serving.kv.cow_splits", {"value": 0})["value"] == 0
    # paged counts one hit per admitted request (vs per chunk skipped in
    # the contiguous path), so assert presence rather than the exact tally
    assert snap["serving.prefix_cache.hits"]["value"] >= 1


def test_paged_cow_split_copies_one_partial_page_per_hit():
    """A prefix hit that ends mid-page pins the shared partial page and
    copies it exactly once, on the hitter's first write (COW): per hit,
    copied bytes <= one page."""
    from repro.obs.flight import flight
    shared = list(range(1, 9))            # 8 tokens: half of a 16-token page
    prompts = [shared + t for t in ([21, 22, 23], [31, 32], [41])]
    toks_c, fin_c, _ = _run_workload(prompts, 3, layout="contiguous",
                                     page_size=16, n_slots=1,
                                     prefix_entries=4)
    flight.enable()
    flight.clear()
    try:
        toks_p, fin_p, eng = _run_workload(prompts, 3, layout="paged",
                                           page_size=16, n_slots=1,
                                           prefix_entries=4)
        events = flight.snapshot()
    finally:
        flight.disable()
    assert toks_p == toks_c and fin_p == fin_c
    snap = eng.metrics_snapshot()
    hits = snap["serving.prefix_cache.hits"]["value"]
    assert hits == 2                      # requests 2 and 3 hit the 8-token entry
    assert snap["serving.kv.cow_splits"]["value"] == hits
    # copies = one COW page per hit + one insert-side copy of the
    # donor's half-written page; never a full prefix copy
    assert snap["serving.kv.pages_copied"]["value"] == hits + 1
    assert snap["serving.kv.pages_shared"]["value"] == hits
    assert [e for e in events if e["kind"] == "kv.cow"]


def test_paged_admission_blocks_until_pages_free():
    """A request only admits when the pool covers its worst case; when it
    can't, it waits (kv.oom flight event, admit_blocked counter) and
    still completes correctly once pages free up."""
    from repro.obs.flight import flight
    prompts = [[i + 1] * 20 for i in range(4)]   # cap 24 tokens = 2 pages
    flight.enable()
    flight.clear()
    try:
        # pool of 4 sixteen-token pages: two in-flight requests fill it
        toks, fins, eng = _run_workload(prompts, 4, layout="paged",
                                        page_size=16, n_slots=4,
                                        kv_pages=4)
        events = flight.snapshot()
    finally:
        flight.disable()
    snap = eng.metrics_snapshot()
    assert snap["serving.kv.admit_blocked"]["value"] > 0
    oom = [e for e in events if e["kind"] == "kv.oom"]
    assert oom and all("need_pages" in e for e in oom)
    assert fins == ["max_new"] * 4
    for out, p in zip(toks, prompts):
        assert out == ref_decode(p, 5), p
    assert eng._kv.pool.free_pages == eng._kv.pool.n_pages   # all released


def test_paged_prefix_eviction_releases_pages():
    """Evicting a prefix entry (capacity pressure) returns its pinned
    pages to the pool and emits a kv.evict flight event."""
    from repro.obs.flight import flight
    # distinct 8-token prefixes -> distinct entries; capacity 1 evicts
    prompts = [[i + 1] * 8 + [40 + i] for i in range(3)]
    flight.enable()
    flight.clear()
    try:
        toks, fins, eng = _run_workload(prompts, 3, layout="paged",
                                        page_size=8, n_slots=1,
                                        prefix_entries=1)
        events = flight.snapshot()
    finally:
        flight.disable()
    snap = eng.metrics_snapshot()
    assert snap["serving.kv.evicted_pages"]["value"] > 0
    assert [e for e in events if e["kind"] == "kv.evict"]
    for out, p in zip(toks, prompts):
        assert out == ref_decode(p, 4), p
    # nothing leaked: free pages + pages still pinned by live entries
    held = sum(len(e.pages) for e in eng.prefix._entries.values())
    assert eng._kv.pool.free_pages + held == eng._kv.pool.n_pages
    eng._kv.pool.check()
