#!/usr/bin/env python3
"""Chip smoke run: the serving engine at phi3-mini width and the SIMT
machine on one TPU chip, through their normal entry points, in one
process.

    python3 chip_smoke.py

Phases (each a function of its config, so tests run them small on the
CPU):

  serving  `repro.launch.serve.main` on phi3-mini-3.8b at full width
           (random bf16 weights from PRNGKey(0)), once with the
           contiguous KV layout and once paged.  Every request must
           finish with max_new + 1 tokens, with no degraded sample, no
           non-finite logit row and some prefix-cache hits.  Tokens must
           equal a sequential `api.forward` prefill + decode on the same
           weights, run one request at a time at the engine's batch
           width and chunk size; a token may differ only where it ties
           the reference's top logit exactly in bf16.  Paged tokens must
           equal the contiguous ones under the same rule.
  simt     Rodinia bfs and vecadd on the cycle-level SIMT machine.  The
           numpy oracle must pass, and every simulated statistic must
           equal benchmarks/baselines/BENCH_fig9_rodinia.json.

Earlier lines report wall, compile and run seconds per phase: reports,
not metrics.  The last line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
When JAX's first device is not a TPU it prints "ok": false and exits 1.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SERVE_ARGV = ["--arch", "phi3-mini-3.8b", "--requests", "8", "--slots", "4",
              "--max-len", "1024", "--max-new", "16", "--shared-prefix", "64"]
N_REF = 2                     # requests checked against the reference
SIMT_BENCHES = ("bfs", "vecadd")
SIMT_CONFIG = (8, 8)          # (warps, threads)
SIMT_BASELINE = os.path.join(ROOT, "benchmarks", "baselines",
                             "BENCH_fig9_rodinia.json")


class CompileMeter:
    """Adds up JAX's backend compile events (seconds and programs; a
    persistent-cache hit counts its retrieval) and the persistent cache's
    hits and writes."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":   # a write
            self.writes += 1

    def snapshot(self):
        return self.compile_s, self.compiles, self.hits, self.writes

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


@contextlib.contextmanager
def timed(label: str, meter: CompileMeter):
    """Prints the wall time of the block, split into backend compile and
    the rest (tracing, set-up and run)."""
    c0, n0, h0, m0 = meter.snapshot()
    w0 = time.perf_counter()
    yield
    wall = time.perf_counter() - w0
    c1, n1, h1, m1 = meter.snapshot()
    mem = jax.devices()[0].memory_stats() or {}
    peak = (f"; device peak {mem['peak_bytes_in_use']} bytes so far"
            if "peak_bytes_in_use" in mem else "")
    print(f"[time] {label}: wall {wall:.3f}s = compile {c1 - c0:.3f}s "
          f"({n1 - n0} programs; persistent cache {h1 - h0} hits, "
          f"{m1 - m0} writes) + rest {wall - (c1 - c0):.3f}s{peak}",
          flush=True)


# ---------------------------------------------------------------- serving

@functools.lru_cache(maxsize=None)
def _reference_fns(cfg):
    """Jitted chunk-append and decode steps of the reference; lane 0's
    logits only."""
    from repro.models import api
    V = cfg.vocab_size

    @functools.partial(jax.jit, donate_argnums=2)
    def chunk(p, toks, c):
        lg, _, c = api.forward(p, {"tokens": toks}, cfg, mode="chunk",
                               caches=c, remat="none")
        return lg[0, :, :V], c

    @functools.partial(jax.jit, donate_argnums=2)
    def decode(p, toks, c):
        lg, _, c = api.forward(p, {"tokens": toks}, cfg, mode="decode",
                               caches=c, remat="none")
        return lg[0, -1, :V], c

    return chunk, decode


def reference_logits(eng, prompt, tokens):
    """Sequential `api.forward` reference on the engine's weights: the
    request alone, prefilled chunk by chunk, then decoded feeding
    `tokens[:-1]`.  Row i holds the logits that predict tokens[i] ->
    float32 numpy [len(tokens), vocab].

    It runs in lane 0 of a batch as wide as the engine's and with the
    engine's chunk size: on a v5e a batch-1 reference, or a one-shot
    prefill, moves the bf16 logits by an ulp or more, enough to reorder
    near-tied tokens (PERF.md, PR 11)."""
    from repro.models import api
    chunk_fn, decode_fn = _reference_fns(eng.cfg)
    B, C = eng.n_slots, eng.chunk
    c = api.init_caches(eng.cfg, B, eng.max_len)
    for pos in range(0, len(prompt), C):
        seg = prompt[pos:pos + C]
        toks = np.zeros((B, C), np.int32)
        toks[0, :len(seg)] = seg
        c["len"] = jnp.zeros(B, jnp.int32).at[0].set(pos)
        lg, c = chunk_fn(eng.params, jnp.asarray(toks), c)
    c["len"] = jnp.zeros(B, jnp.int32).at[0].set(len(prompt))
    rows = [lg[len(seg) - 1]]
    for t in tokens[:-1]:
        toks = np.zeros((B, 1), np.int32)
        toks[0, 0] = t
        row, c = decode_fn(eng.params, jnp.asarray(toks), c)
        rows.append(row)
    return np.asarray(jnp.stack(rows).astype(jnp.float32))


def is_top(row, tok) -> bool:
    """True when `tok`'s logit equals the row's largest logit."""
    return bool(row[tok] == row.max())


def check_against_reference(rows, toks, label) -> int:
    """Each token must be the reference's argmax, or tie its top logit
    exactly.  Returns the number of ties (each is printed)."""
    ties = 0
    for i, (row, tok) in enumerate(zip(rows, toks)):
        if tok == int(row.argmax()):
            continue
        if not is_top(row, tok):
            raise AssertionError(
                f"{label} step {i}: token {tok} (reference logit "
                f"{row[tok]}) != reference argmax {int(row.argmax())} "
                f"(logit {row.max()})")
        ties += 1
        print(f"[tie] {label} step {i}: token {tok} and reference argmax "
              f"{int(row.argmax())} share the top logit {row.max()}",
              flush=True)
    return ties


def check_serving_run(eng, max_new: int) -> dict:
    """Every request finished with max_new + 1 tokens, nothing degraded,
    no non-finite logit rows, prefix-cache hits > 0, and the engine's
    arrays live on JAX's default device."""
    snap = eng.metrics_snapshot()

    def val(key):
        return snap[key]["value"]

    for rid, req in sorted(eng.requests.items()):
        assert req.done and req.finish_reason == "max_new", \
            (rid, req.finish_reason)
        assert len(req.out) == max_new + 1, (rid, len(req.out))
    assert snap.get("serving.degraded_samples", {"value": 0})["value"] == 0
    assert val("serving.decode.nonfinite_logit_rows") == 0
    assert val("serving.prefix_cache.hits") > 0
    dev = jax.devices()[0]
    for leaf in jax.tree.leaves((eng.params, eng.caches)):
        assert leaf.devices() == {dev}, leaf.devices()
    recompiles = {k: v["value"] for k, v in sorted(snap.items())
                  if k.startswith("serving.recompiles.")}
    print(f"[check] {len(eng.requests)} requests done, "
          f"{max_new + 1} tokens each; degraded 0; non-finite rows 0; "
          f"prefix hits {val('serving.prefix_cache.hits')}; "
          f"recompiles {recompiles}", flush=True)
    return recompiles


def serving_phase(argv, meter: CompileMeter, n_ref: int = N_REF) -> None:
    """`serve.main(argv)` with the contiguous, then the paged KV layout;
    checks both runs and their tokens (see the module docstring)."""
    from repro.launch import serve
    max_new = int(argv[argv.index("--max-new") + 1])
    outs = {}
    for layout in ("contiguous", "paged"):
        with timed(f"serving/{layout} serve.main", meter):
            rc = serve.main(argv + ["--kv-layout", layout])
        eng, serve.last_engine = serve.last_engine, None
        assert rc == 0, rc
        check_serving_run(eng, max_new)
        outs[layout] = {rid: list(r.out) for rid, r in eng.requests.items()}
        prompts = {rid: r.prompt for rid, r in eng.requests.items()}
        eng.caches = eng.prefix = None     # room for the reference's KV
        if layout == "contiguous":
            with timed("serving/contiguous reference", meter):
                for rid in sorted(outs[layout])[:n_ref]:
                    rows = reference_logits(eng, prompts[rid],
                                            outs[layout][rid])
                    ties = check_against_reference(
                        rows, outs[layout][rid], f"request {rid}")
                    print(f"[check] request {rid}: tokens match the "
                          f"sequential reference ({ties} bf16 ties)",
                          flush=True)
        else:
            compare_layouts(eng, prompts, outs["contiguous"], outs["paged"])
        # free the weights before the next layout builds its own
        eng.params = None
        del eng
        gc.collect()


def compare_layouts(eng, prompts, contig, paged) -> None:
    """Paged tokens equal contiguous ones; at a request's first
    difference both tokens must tie the reference's top logit."""
    same = 0
    for rid in sorted(contig):
        a, b = contig[rid], paged[rid]
        if a == b:
            same += 1
            continue
        i = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
        row = reference_logits(eng, prompts[rid], a[:i + 1])[i]
        if not (is_top(row, a[i]) and is_top(row, b[i])):
            raise AssertionError(
                f"request {rid} step {i}: paged token {b[i]} (logit "
                f"{row[b[i]]}) != contiguous token {a[i]} (logit "
                f"{row[a[i]]}); reference top logit {row.max()}")
        print(f"[tie] request {rid} step {i}: paged {b[i]} and contiguous "
              f"{a[i]} share the reference's top logit {row.max()}",
              flush=True)
    print(f"[check] paged == contiguous on {same}/{len(contig)} requests",
          flush=True)


# ------------------------------------------------------------------- simt

def simt_phase(benches, config, meter: CompileMeter,
               baseline: str = SIMT_BASELINE) -> None:
    """Each bench twice at one (warps, threads): the oracle passes, and
    every simulated statistic equals the baseline, on both calls."""
    from benchmarks.fig9_rodinia import BENCHES, machine_config
    from repro.runtime.kernels_src import rodinia
    with open(baseline) as f:
        want_all = json.load(f)
    w, t = config
    for name in benches:
        kw, miss_latency = BENCHES[name]
        mc = machine_config(w, t, miss_latency)
        want = want_all[f"{name}/{w}w{t}t"]["stats"]
        for call in ("first call", "second call"):
            # stats are read back to the host: the timer covers the run
            with timed(f"simt/{name} {w}w{t}t {call}", meter):
                res, ok = rodinia.BENCHMARKS[name](mc, **kw)
            assert ok, f"{name} failed its numpy oracle check"
            diff = {k: (res.stats.get(k), v) for k, v in want.items()
                    if res.stats.get(k) != v}
            assert res.stats.keys() == want.keys() and not diff, \
                f"{name} {w}w{t}t stats differ from the baseline: {diff}"
        print(f"[check] simt/{name} {w}w{t}t: oracle ok; all "
              f"{len(want)} statistics equal the baseline (cycles "
              f"{res.stats['cycles']}, instrs {res.stats['instrs']})",
              flush=True)


# ------------------------------------------------------------------- main

def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[device] {devs} platform={device['platform']} "
          f"kind={device['kind']} count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print(json.dumps({"ok": False, "device": device,
                          "error": "JAX's first device is not a TPU"}))
        return 1
    print(f"[cache] persistent compilation cache: {cache_dir}", flush=True)
    meter = CompileMeter()
    with timed("phase serving", meter):
        serving_phase(SERVE_ARGV, meter)
    with timed("phase simt", meter):
        simt_phase(SIMT_BENCHES, SIMT_CONFIG, meter)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception as e:
        traceback.print_exc()
        sys.stdout.flush()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}))
        rc = 1
    sys.exit(rc)
