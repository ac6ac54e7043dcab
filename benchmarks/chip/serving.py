"""Serving cells: the program's `repro.serving.engine.Engine`, built as
`repro.launch.serve` builds it, driven through `submit` and `step` by
the mix's schedule, and checked against the plain reference.

One run:

  set-up   weights made on the device from the seed in one jitted call
           (the benchmark's own draw, in the program's parameter
           layout); the engine; a warm-up that compiles every program
           the window will run: the chunk and decode steps through
           `submit`/`step`, and the prefix-cache snapshot read and write
           for every chunk multiple the mix can store.  A closed loop
           then fills its slots as a loop that has run for long holds
           them: one request per slot, answers drawn from the stationary
           residual (traffic.residual), stepped until each has its first
           token.
  window   `seconds` of traffic.  Every `Engine.step()` is stamped; the
           stamps give each request's first token and the gaps between
           its tokens.  An open-loop mix then drains: requests due in
           the window are stepped on (no new arrivals) until each has
           its first token, at most `DRAIN_S` seconds.
  check    a sample of finished requests, drawn from the seed: the
           longest, and one of every other slot, so that a fault in some
           lanes shows; each runs through the float32 reference over
           prompt + served tokens: the widest gap by which a served
           token's reference logit lies below the reference's best.

Nothing here knows a model family.  A configuration's `family` names its
adapter `program/<family>.py` (the program's ModelConfig, and the
reference's names for the program's weights), its plain reference
`reference/<family>.py` and its work functions `work/<family>.py`.
"""
from __future__ import annotations

import heapq
import math
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.chip import bench, traffic, trace_reduce

DRAIN_S = 60.0
CHECK_TOKENS = 400          # served tokens the check samples, at least


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- program

def program(cfg: Dict[str, Any]):
    """The configuration's family adapter, `program/<family>.py`:
    `model_config(cfg)` gives the program's ModelConfig, and
    `reference_weights(params)` the reference's names for the arrays of
    the program's parameters."""
    return bench.load_module(f"program/{cfg['family']}.py")


def make_weights(pcfg, seed: int):
    """The benchmark's weights, drawn from the seed on the device in one
    jitted call, in the bf16 layout the program's `build_params` gives
    (its shapes only are asked for): normal with std 1/sqrt(fan_in),
    the embedding std 0.02, norm scales 1."""
    import jax
    import jax.numpy as jnp
    from repro.models import api
    shapes = jax.eval_shape(
        lambda: api.build_params(jax.random.PRNGKey(0), pcfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            name = str(getattr(path[-1], "key", ""))
            if "norm" in name:
                leaves.append(jnp.ones(s.shape, s.dtype))
                continue
            std = 0.02 if name == "embed" else 1.0 / math.sqrt(s.shape[-2])
            x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32) * std
            leaves.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.wrap_key_data(
        np.asarray(bench.seed_words(seed), np.uint32))
    return jax.jit(draw)(key)


def engine_settings(cfg: Dict[str, Any], mix: Dict[str, Any]
                    ) -> Dict[str, Any]:
    """The configuration's engine settings, with the sizing a mix's
    deployment sets for itself (`engine` in the traffic file)."""
    return dict(cfg["engine"], **mix.get("engine", {}))


def build_engine(cfg, pcfg, params, settings):
    from repro.serving.engine import Engine
    from repro.serving.sampler import SamplerConfig
    return Engine(pcfg, params, n_slots=settings["n_slots"],
                  max_len=cfg["max_len"],
                  sampler=SamplerConfig(temperature=0.0, seed=0),
                  eos_id=-1, prefill_chunk=settings["prefill_chunk"],
                  prefix_cache_entries=settings["prefix_cache_entries"],
                  kv_layout=settings["kv_layout"])


def warm_up(eng, cfg, longest_prompt: int, system: List[int],
            annotate) -> None:
    """Compile every program the window runs: two requests through
    `submit`/`step` (chunk and decode steps; with a system prompt, it is
    sent once, as a running server would already hold it), then the
    prefix-cache snapshot read and masked write for every chunk
    multiple a prompt of the mix can store."""
    rng = np.random.default_rng(0)
    V = cfg["vocab_size"]
    first = system or []
    for extra in (1, 33):
        p = first + rng.integers(traffic.FIRST_TOKEN_ID, V, extra).tolist()
        eng.submit(p, max_new=2)
    with annotate("warm_up"):
        eng.run(max_ticks=10_000)
    C = eng.chunk
    if eng.prefix is None or not hasattr(eng, "_read_slot"):
        return
    sel = np.zeros(eng.n_slots, bool)
    for n in range(C, longest_prompt, C):
        snap = eng._read_slot(0, n)
        eng._write_slots_masked(snap, sel)
    import jax
    jax.block_until_ready(eng.caches)


# ---------------------------------------------------------------- window

class Tracker:
    """Stamps every token each tracked request receives: the time of the
    `Engine.step()` that returned it."""

    def __init__(self, eng):
        self.eng = eng
        self.live: Dict[int, Dict[str, Any]] = {}
        self.all: List[Dict[str, Any]] = []

    def submit(self, prompt, max_new, due: float, **tag) -> Dict[str, Any]:
        rid = self.eng.submit(prompt, max_new=max_new)
        rec = {"rid": rid, "req": self.eng.requests[rid], "due": due,
               "stamps": [], **tag}
        self.live[rid] = rec
        self.all.append(rec)
        return rec

    def stamp(self, now: float) -> List[Dict[str, Any]]:
        """Record new tokens; returns the requests that finished."""
        done = []
        for rid, rec in list(self.live.items()):
            req = rec["req"]
            new = len(req.out) - len(rec["stamps"])
            if new > 0:
                rec["stamps"].extend([now] * new)
            if req.done:
                rec["end"] = now
                done.append(rec)
                del self.live[rid]
        return done


class Window:
    """One window of traffic on a warmed engine."""

    def __init__(self, eng, tracer: Optional["bench.TraceWindow"] = None):
        import jax
        self.eng = eng
        self.tracker = Tracker(eng)
        self.step_log: List[Dict[str, Any]] = []
        self.tracer = tracer or bench.TraceWindow(False)
        self.annotate = jax.profiler.TraceAnnotation

    def tick(self) -> float:
        tr = self.tracker
        before = {rid: len(rec["req"].out) for rid, rec in tr.live.items()}
        traced = self.tracer.on
        with self.annotate("engine.step"):
            self.eng.step()
        now = time.perf_counter()
        spans, prompts = [], []
        for rid, n0 in before.items():
            req = tr.live[rid]["req"]
            P = len(req.prompt)
            for j in range(n0, len(req.out)):
                if j == 0:
                    prompts.append(P)
                else:
                    spans.append(P + j)
        self.step_log.append({"t": now, "spans": spans, "prompts": prompts,
                              "traced": traced})
        return now

    def submit(self, prompt, max_new, due, **tag):
        with self.annotate("submit"):
            return self.tracker.submit(prompt, max_new, due, **tag)

    def fill(self, pool, n_slots: int) -> None:
        """Set-up of a closed loop: the first `n_slots` requests of the
        pool (those a long-running loop has in service) go in, and the
        engine steps until each has its first token."""
        self.pool = iter(pool)
        for _ in range(n_slots):
            r = next(self.pool)
            self.submit(r["prompt"], r["max_new"], due=time.perf_counter())
        while any(not rec["stamps"] for rec in self.tracker.live.values()):
            self.tracker.stamp(self.tick())

    def run_closed(self, mix, seconds: float, n_slots: int,
                   min_done_tokens: int = 0) -> None:
        """Closed loop on the filled slots: the other clients' requests
        wait from the window's start; each finished request's client
        sends the next, until the close."""
        self.t0 = now = time.perf_counter()
        t_end = self.t0 + seconds
        for _ in range(mix["clients"] - n_slots):
            r = next(self.pool)
            self.submit(r["prompt"], r["max_new"], due=now)
        while now < t_end:
            self.tracer.before_call(now, self.t0, t_end)
            now = self.tick()
            for _rec in self.tracker.stamp(now):
                if now < t_end:
                    r = next(self.pool)
                    self.submit(r["prompt"], r["max_new"], due=now)
        self.tracer.stop()
        self.t_close = now
        # for the check only: step on without new requests until every
        # slot has finished a request and `min_done_tokens` served tokens
        # are in finished requests, at most DRAIN_S seconds
        def short():
            done = [r["req"] for r in self.tracker.all if r["req"].done]
            return (len({q.slot for q in done}) < n_slots
                    or sum(len(q.out) for q in done) < min_done_tokens)
        while short() and self.tracker.live and now < self.t_close + DRAIN_S:
            now = self.tick()
            self.tracker.stamp(now)

    def run_sessions(self, sched, seconds: float) -> None:
        """Open loop: each session's first turn is due at its start; turn
        k+1 is due think_s after turn k's answer.  Arrivals stop at the
        close; then the window drains, stepping on (traced no more)
        until every request due in it has its first token, at most
        DRAIN_S seconds."""
        system = sched["system"]
        self.t0 = now = time.perf_counter()
        t_end = self.t0 + seconds
        events = [(self.t0 + s["start_s"], i, 0)
                  for i, s in enumerate(sched["sessions"]) if s["turns"]]
        heapq.heapify(events)
        history: Dict[int, List[int]] = {}
        while True:
            self.tracer.before_call(now, self.t0, t_end)
            while events and events[0][0] <= now:
                due, i, k = heapq.heappop(events)
                if due >= t_end:
                    continue
                turn = sched["sessions"][i]["turns"][k]
                self.submit(history.get(i, system) + turn["user"],
                            turn["max_new"], due=due, session=i, turn=k)
            if now >= t_end:
                break
            if not self.tracker.live:
                nxt = min(events[0][0] if events else t_end, t_end)
                with self.annotate("wait_for_arrival"):
                    time.sleep(max(0.0, nxt - now))
                now = time.perf_counter()
                continue
            now = self.tick()
            for rec in self.tracker.stamp(now):
                i, k = rec["session"], rec["turn"]
                history[i] = rec["req"].prompt + rec["req"].out
                turns = sched["sessions"][i]["turns"]
                if k + 1 < len(turns):
                    heapq.heappush(events,
                                   (now + turns[k]["think_s"], i, k + 1))
        self.tracer.stop()
        self.t_close = now
        while (any(not r["stamps"] for r in self.tracker.live.values())
               and now < self.t_close + DRAIN_S):
            now = self.tick()
            self.tracker.stamp(now)

    def end_to_end(self, open_loop: bool) -> Dict[str, Any]:
        """The window's end-to-end numbers and the counts behind them."""
        reqs, t0, t_close = self.tracker.all, self.t0, self.t_close
        ttft = [(r["stamps"][0] - r["due"]) if r["stamps"] else math.inf
                for r in reqs]
        gaps, out_tokens = [], 0
        for r in reqs:
            st = [t for t in r["stamps"] if t0 <= t <= t_close]
            out_tokens += len(st)
            gaps.extend(b - a for a, b in zip(st, st[1:]))
        # a request fails when it finishes other than by max_new (timeout,
        # shed, degraded), or, in an open loop, has no first token after
        # the drain; a closed loop's requests still running at the close
        # are not due an answer yet
        failed = sum(1 for r in reqs if r["req"].done
                     and r["req"].finish_reason != "max_new")
        if open_loop:
            failed += sum(1 for r in reqs if not r["stamps"])
        window = t_close - t0
        return {"window_s": window, "requests": len(reqs),
                "finished": sum(1 for r in reqs if r["req"].done),
                "failed": failed, "out_tokens": out_tokens,
                "tokens_per_s": out_tokens / window,
                "gaps": gaps, "ttft": ttft,
                "itl_p95_ms": (bench.quantile(gaps, 0.95) * 1e3
                               if gaps else None),
                "ttft_p95_ms": (bench.quantile(ttft, 0.95) * 1e3
                                if open_loop and ttft else None),
                "lateness": [r["req"].submit_t - r["due"] for r in reqs]}


def report(e2e: Dict[str, Any], compiles: int) -> None:
    gaps, ttft = e2e["gaps"], e2e["ttft"]
    log(f"[window] {e2e['window_s']:.3f} s; {e2e['requests']} requests "
        f"due, {e2e['finished']} finished, {e2e['failed']} failed; "
        f"{e2e['out_tokens']} tokens; compiles inside the window: "
        f"{compiles}")
    if gaps:
        med = bench.quantile(gaps, .5)
        long_share = sum(g > 1.5 * med for g in gaps) / len(gaps)
        log(f"[window] gap median {med * 1e3:.3f} ms, "
            f"p95 {bench.quantile(gaps, .95) * 1e3:.3f} ms over "
            f"{len(gaps)} gaps; {long_share * 100:.2f} % of gaps over 1.5 x "
            f"the median")
    fin = [t for t in ttft if math.isfinite(t)]
    if fin:
        log(f"[window] ttft median {bench.quantile(ttft, .5) * 1e3:.3f} ms,"
            f" p95 {bench.quantile(ttft, .95) * 1e3:.3f} ms over "
            f"{len(ttft)} requests ({len(ttft) - len(fin)} without a first "
            f"token); max {max(fin) * 1e3:.3f} ms")
    late = e2e["lateness"]
    if late:
        log(f"[window] generator lateness (submit - due): median "
            f"{bench.quantile(late, .5) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms")


def prepare(cfg, mix, seed: int, seconds: float, t_process: float):
    """Set-up: weights, engine, schedule, warm-up, and a closed loop's
    slots filled.  -> (window, params, schedule)."""
    import jax
    pcfg = program(cfg).model_config(cfg)
    params = make_weights(pcfg, seed)
    jax.block_until_ready(params)
    bench.phase("weights", t_process)
    settings = engine_settings(cfg, mix)
    eng = build_engine(cfg, pcfg, params, settings)
    bench.phase("engine", t_process)
    V, max_len = cfg["vocab_size"], cfg["max_len"]
    if mix["loop"] == "closed":
        sched = traffic.closed_requests(mix, seed, mix["clients"] * 64, V,
                                        max_len, settings["n_slots"])
        system: List[int] = []
        longest = mix["prompt_len"]["hi"]
    else:
        sched = traffic.sessions(mix, seed, seconds, V, max_len)
        system = sched["system"]
        longest = max_len
    warm_up(eng, cfg, longest, system, jax.profiler.TraceAnnotation)
    bench.phase("warm-up", t_process)
    win = Window(eng)
    if mix["loop"] == "closed":
        win.fill(sched, settings["n_slots"])
        bench.phase("slots filled", t_process)
    return win, params, sched


def run_window(win: Window, mix, sched, seconds: float,
               min_done_tokens: int) -> None:
    if mix["loop"] == "closed":
        win.run_closed(mix, seconds, win.eng.n_slots, min_done_tokens)
    else:
        win.run_sessions(sched, seconds)


def run_cell(cell: Dict[str, Any], cfg: Dict[str, Any], mix: Dict[str, Any],
             *, seed: int, seconds: float, trace: bool, t_process: float,
             meter, device: Dict[str, Any], spec_metrics: List[Dict],
             limits: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    limits = limits or bench.limits(cell["name"])
    min_tokens = int(limits["min_checked_tokens"])
    win, params, sched = prepare(cfg, mix, seed, seconds, t_process)
    eng = win.eng
    m0 = eng.metrics_snapshot()
    setup_s = time.perf_counter() - t_process
    log(f"[setup] {setup_s:.3f} s (compiles so far {meter.compiles}, "
        f"{meter.compile_s:.3f} s; persistent-cache hits {meter.hits})")
    compiles0 = meter.compiles
    win.tracer = bench.TraceWindow(trace)
    run_window(win, mix, sched, seconds, min_tokens)
    compiles = meter.compiles - compiles0
    m1 = eng.metrics_snapshot()
    mem_peak = bench.memory_peak_bytes(device["count"])
    e2e = win.end_to_end(open_loop=mix["loop"] != "closed")
    report(e2e, compiles)
    values = {"setup_s": setup_s, "tokens_per_s": e2e["tokens_per_s"],
              "itl_p95_ms": e2e["itl_p95_ms"],
              "ttft_p95_ms": e2e["ttft_p95_ms"]}

    ctx = {"cfg": cfg, "mix": mix, "device": device,
           "window_s": e2e["window_s"], "t0": win.t0,
           "peaks": bench.peaks_for(device["kind"]) if trace else None,
           "work": bench.load_module(f"work/{cfg['family']}.py"),
           "counters_before": m0, "counters_after": m1,
           "step_log": win.step_log, "requests": win.tracker.all,
           "trace": None}
    breakdown = None
    dev_extra: Dict[str, Any] = {}
    if trace and win.tracer.dir:
        tr = trace_reduce.load(win.tracer.dir)
        ctx["trace"] = tr
        dev_extra = {"busy_s": trace_reduce.busy_s(tr),
                     "window_s": trace_reduce.window_s(tr)}
        breakdown = trace_reduce.breakdown(tr)
        import shutil
        shutil.rmtree(win.tracer.dir, ignore_errors=True)
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in spec_metrics:
        v = (bench.metric_reader(m["name"])(ctx) if trace
             else values.get(m["name"]))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            log(f"[metric] {m['name']} = {v} {m['unit']}")

    n_slots = eng.n_slots
    free_engine(win)
    del eng
    compared = check(win.tracker.all, params, cfg, seed)
    limit = float(limits["max_logit_gap"])
    # a closed loop keeps every slot busy and drains until each has
    # finished a request, so its sample holds every slot; an open loop's
    # holds every slot that finished one
    finished_slots = len({r["req"].slot for r in win.tracker.all
                          if r["req"].done})
    need_slots = n_slots if mix["loop"] == "closed" else finished_slots
    compared_out = {
        "max_logit_gap": {"value": compared["max_gap"], "limit": limit},
        "checked_tokens": {"value": compared["tokens"], "limit": min_tokens},
        "checked_slots": {"value": compared["slots"], "limit": need_slots},
    }
    correct = (compared["max_gap"] <= limit
               and compared["tokens"] >= min_tokens
               and compared["slots"] >= need_slots)
    return {"correct": correct, "attempted": e2e["requests"],
            "failed": e2e["failed"], "metrics": metrics,
            "device": dict(device, memory_peak_bytes=mem_peak, **dev_extra),
            "breakdown": breakdown, "compared": compared_out}


def free_engine(win: Window) -> None:
    """Drop the engine's KV and every reference to the engine, so the
    reference runs beside the weights alone."""
    import gc
    win.eng.caches = None
    win.eng.prefix = None
    win.eng = win.tracker.eng = None
    gc.collect()


def readings(cfg, mix, seed: int, seconds: float, min_done_tokens: int = 0
             ) -> Dict[str, Any]:
    """What a limit is set from: one window of the mix, then on the same
    sample of finished requests the program's widest gap and the
    control's (calibrate.py).  Everything it put on the device but the
    weights is freed before the reference runs, and all of it when it
    returns."""
    win, params, sched = prepare(cfg, mix, seed, seconds, time.perf_counter())
    run_window(win, mix, sched, seconds, min_done_tokens)
    free_engine(win)
    reqs = win.tracker.all
    return {"program": check(reqs, params, cfg, seed),
            "control": check(reqs, params, cfg, seed, control=True)}


def pick_sample(reqs, seed: int) -> List[Dict[str, Any]]:
    """Finished requests to check: the one with the most served tokens;
    then, in an order drawn from the seed, one of every other slot that
    finished a request; then more in that order while the sample holds
    fewer than CHECK_TOKENS served tokens."""
    done = [r for r in reqs if r["req"].done]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["req"].out))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(bench.seed_words(seed, 1)[0]).permutation(
        len(rest))
    out, slots = [longest], {longest["req"].slot}
    for i in order:
        if rest[i]["req"].slot not in slots:
            out.append(rest[i])
            slots.add(rest[i]["req"].slot)
    for i in order:
        if sum(len(r["req"].out) for r in out) >= CHECK_TOKENS:
            break
        if not any(r is rest[i] for r in out):
            out.append(rest[i])
    return out


def check(reqs, params, cfg, seed, control: bool = False) -> Dict[str, Any]:
    ref = bench.load_module(f"reference/{cfg['family']}.py")
    w = program(cfg).reference_weights(params)
    sample = pick_sample(reqs, seed)
    worst, tokens = 0.0, 0
    for r in sample:
        req = r["req"]
        g = ref.served_gaps(w, cfg, req.prompt, req.out, cfg["max_len"],
                            control=control)
        tokens += len(g)
        worst = max(worst, float(g.max()))
    slots = len({r["req"].slot for r in sample})
    log(f"[check] {len(sample)} requests from {slots} slots, {tokens} "
        f"served tokens; widest gap below the reference's best logit "
        f"{worst}")
    return {"max_gap": worst, "tokens": tokens, "requests": len(sample),
            "slots": slots}
