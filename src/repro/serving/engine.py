"""Serving engine: continuous batching over a slotted KV-cache pool.

Decode: one jitted step over the whole pool; the RequestScheduler (the
Vortex 4-mask warp scheduler over request slots) decides which slots
advance each tick.  Slots not selected keep their state — the decode
runs the full pool with a lane mask, exactly how a thread mask
predicates lanes.

Prefill (the stalled-warp fill path) is **chunked and batched**:
prompts stream into the decode pool's caches in fixed-size chunks
through ONE jitted chunk function — no per-bucket recompiles, long
prompts interleave with decode ticks instead of head-of-line blocking
them, and every stalled slot advances in the same batched call.  A
chunk-hash **prefix cache** (serving/prefix_cache.py) short-circuits
shared prompt prefixes entirely: matching KV prefixes are copied from a
bounded LRU pool into the slot via `_write_slot`, no forward pass at
all.  Families without a chunk-appendable cache (recurrent state, stub
frontends, ring windows) fall back to the legacy per-request bucketed
prefill (`prefill_mode="legacy"`), which is also the baseline the
serving benchmark measures speedups against.

Ragged lengths: the cache pool's `len` is a per-slot [B] vector (see
models/attention.py decode path).

KV layout (`kv_layout=`): "contiguous" gives every slot a private
[max_len] slab; "paged" (serving/kv_pool.py) keeps KV in a fixed pool
of fixed-size pages addressed through per-slot block tables — prefix
hits PIN shared pages (refcount bump) instead of copying, only the
last partial page of a shared prefix is ever copied (copy-on-write),
and admission requires the pool to cover a request's worst case.  The
jitted paged steps gather the contiguous view from the pool, run the
unchanged model forward, and scatter back only dirty pages — greedy
decode is bit-identical across layouts (gated by tests).

Failure semantics (serving/README.md "Failure semantics"): per-request
deadlines/TTLs (finish reason "timeout"), a bounded admission queue with
a shed policy ("shed"), an in-jit NaN/Inf logit guard that degrades to
greedy sampling ("degraded"), and a watchdog around `step()` that
retries transient failures with capped exponential backoff.  All hooks
accept an optional `repro.faults.FaultInjector` and are exact no-ops —
bit-identical serving — when no faults are injected.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.faults.plan import FaultInjector, TransientFault
from repro.obs.flight import flight

# Perfetto pid for request-scoped timeline tracks: each rid gets its own
# tid under this pid, so traces show one row per request (admission ->
# queue wait -> prefill chunks -> decode -> finish)
_REQ_TRACK_PID = 1
from repro.models import api
from repro.serving import kv_pool
from repro.serving.kv_pool import PagedKV, PagePool
from repro.serving.prefix_cache import PrefixCache, PrefixEntry
from repro.serving.sampler import (SamplerConfig, logit_entropy,
                                   sample_guarded)
from repro.serving.scheduler import RequestScheduler


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    done: bool = False
    # "eos" | "max_new" | "max_len" | "timeout" | "shed" | "degraded"
    finish_reason: str = ""
    submit_t: float = 0.0
    admit_t: float = 0.0
    first_tok_t: float = 0.0
    last_tok_t: float = 0.0
    deadline_s: Optional[float] = None   # TTL from submit; None = no deadline
    degraded: bool = False               # sampled through the NaN/Inf guard


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 max_len: int = 512, prompt_bucket: int = 64,
                 decode_width: Optional[int] = None,
                 sampler: SamplerConfig = SamplerConfig(),
                 eos_id: int = 1,
                 prefill_chunk: int = 32,
                 prefill_mode: str = "auto",
                 prefix_cache_entries: int = 32,
                 kv_layout: str = "contiguous",
                 kv_page_size: int = 32,
                 kv_pages: Optional[int] = None,
                 faults: Optional[FaultInjector] = None,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject-new",
                 default_deadline_s: Optional[float] = None,
                 step_retries: int = 3,
                 retry_base_s: float = 0.01,
                 retry_max_s: float = 0.25,
                 tick_budget_s: Optional[float] = None):
        """prefill_mode: 'chunked' | 'legacy' | 'auto' (chunked when the
        model family supports chunk-append cache writes and the cache
        layout is non-ring).  prefix_cache_entries bounds the LRU pool
        of KV prefix snapshots; 0 disables prefix caching entirely.

        kv_layout: 'contiguous' (default — every slot owns a private
        [max_len] KV slab) or 'paged' (KV lives in a fixed pool of
        `kv_pages` pages of `kv_page_size` tokens; slots hold block
        tables; prefix-cache hits PIN shared pages instead of copying,
        with copy-on-write on the last partial page — see
        serving/kv_pool.py).  Paged requires chunked prefill.  The
        default pool size gives every slot its worst case plus one page
        of headroom, so admission never deadlocks; smaller pools admit
        only when the pool covers a request's worst case, evicting LRU
        prefix entries under pressure.

        Failure semantics (see serving/README.md):
          faults              optional FaultInjector; every hook is a
                              no-op `is not None` check when absent
          max_queue           bound on the pending admission queue; a
                              submit beyond it is SHED per `shed_policy`
                              ("reject-new" sheds the incoming request,
                              "drop-oldest" sheds the queue head)
          default_deadline_s  TTL applied to requests submitted without
                              an explicit deadline; expired requests
                              finish with reason "timeout"
          step_retries        watchdog: transient step failures retry up
                              to this many times with capped exponential
                              backoff (retry_base_s doubling, capped at
                              retry_max_s) before re-raising
          tick_budget_s       ticks slower than this bump the
                              serving.watchdog.slow_ticks counter
        """
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.bucket = prompt_bucket
        self.decode_width = decode_width or n_slots
        self.sampler = sampler
        self.eos_id = eos_id
        self.sched = RequestScheduler(n_slots)
        self.requests: Dict[int, Request] = {}
        self.pending: Deque[Request] = deque()
        self._slot_req: Dict[int, Request] = {}
        self._next_rid = 0
        self._key = jax.random.PRNGKey(sampler.seed)
        # per-engine telemetry: host-side only — the jitted prefill/decode
        # functions are untouched, so enabling/disabling metrics never
        # changes jit cache behavior
        self.metrics = obs.Registry()
        self._t_start = time.perf_counter()
        # watchdog-tick liveness: beaten at the top of every step()
        # attempt; the HTTP plane's /healthz derives health from it
        self.liveness = obs.Liveness()
        # failure hardening (all off by default — fault-free serving is
        # bit-identical to the unhardened engine)
        self.faults = faults
        self.max_queue = max_queue
        assert shed_policy in ("reject-new", "drop-oldest")
        self.shed_policy = shed_policy
        self.default_deadline_s = default_deadline_s
        self.step_retries = step_retries
        self.retry_base_s = retry_base_s
        self.retry_max_s = retry_max_s
        self.tick_budget_s = tick_budget_s
        self._any_deadlines = False

        if prefill_mode == "auto":
            ring = (cfg.sliding_window is not None
                    and cfg.sliding_window < max_len)
            prefill_mode = ("chunked" if api.supports_chunked_prefill(cfg)
                            and not ring else "legacy")
        assert prefill_mode in ("chunked", "legacy")
        self.prefill_mode = prefill_mode
        self.chunk = prefill_chunk
        if prefill_mode == "chunked":
            assert max_len % prefill_chunk == 0, \
                "max_len must be a multiple of prefill_chunk (chunk " \
                "writes must never cross the cache capacity boundary)"
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(prefill_chunk, prefix_cache_entries)
            if prefill_mode == "chunked" and prefix_cache_entries > 0
            else None)
        # per-slot prefill cursor (# prompt tokens already in the cache)
        # and the prompt's chunk-hash chain, kept while the slot prefills
        self._prefill_pos: Dict[int, int] = {}
        self._chunk_hashes: Dict[int, List[str]] = {}
        self._last_oom_rid = -1

        # structural slot-axis map: the axis whose size changes with the
        # slot count (shape-matching heuristics collide when e.g.
        # num_layers == n_slots)
        s_a = jax.eval_shape(lambda: api.init_caches(cfg, n_slots, max_len))
        s_b = jax.eval_shape(
            lambda: api.init_caches(cfg, n_slots + 1, max_len))
        def axis_of(a, b):
            for ax, (da, db) in enumerate(zip(a.shape, b.shape)):
                if da != db:
                    return ax
            return None
        self._slot_ax = jax.tree.map(axis_of, s_a, s_b)
        # init_caches' `len` is slot-count-independent, so axis_of sees
        # no slot axis — but the engine replaces it with a per-slot [B]
        # vector above.  Without this pin the masked merge would pass
        # the +1'd len through for UNSELECTED lanes, silently shifting
        # the write offset of any slot that sits out a decode tick
        # (exactly what chunk-prefilling slots do).
        self._slot_ax["len"] = 0

        assert kv_layout in ("contiguous", "paged")
        self.kv_layout = kv_layout
        self._kv: Optional[PagedKV] = None
        if kv_layout == "paged":
            assert self.prefill_mode == "chunked", \
                "paged KV requires chunked prefill (the legacy bucketed " \
                "path writes whole [1, bucket] slabs, not pages)"
            assert kv_page_size > 0 and max_len % kv_page_size == 0, \
                "max_len must be a multiple of kv_page_size (block " \
                "tables cover whole pages)"
            pps = max_len // kv_page_size
            if kv_pages is None:
                # worst case for every slot plus one page of headroom
                # each: admission can always succeed once prefix entries
                # are evicted, so paged scheduling never diverges from
                # contiguous under the default sizing
                kv_pages = n_slots * (pps + 1)
            self._kv = PagedKV(PagePool(kv_pages, kv_page_size),
                               n_slots, pps)
            # the device pool: contiguous leaves with (slot, seq) axes
            # replaced by (n_pages + 1 trash, page_size); `len` is not a
            # pool leaf — the host `self.lens` is threaded through the
            # jitted steps as a traced argument instead
            self._pool_ax = {k: v for k, v in self._slot_ax.items()
                             if k != "len"}
            spec_tree = {k: v for k, v in s_a.items() if k != "len"}

            def mk(spec, ax):
                if ax is None or spec.shape[ax + 1] != max_len:
                    raise ValueError(
                        "kv_layout='paged' needs every cache leaf laid "
                        f"out [.., slot, seq={max_len}, ..]; got "
                        f"{spec.shape} (slot axis {ax}) — use contiguous")
                return jnp.zeros(kv_pool.paged_leaf_shape(
                    spec.shape, ax, kv_pages, kv_page_size), spec.dtype)

            self.caches = jax.tree.map(mk, spec_tree, self._pool_ax)
            if self.prefix is not None:
                # paged entries hold ref-counted page chains; eviction
                # (LRU overflow or pool pressure) releases them here
                self.prefix.on_evict = self._on_prefix_evict
        else:
            # pool caches: per-slot len vector (self.lens is its mirror)
            self.caches = api.init_caches(cfg, n_slots, max_len)
            self.caches["len"] = jnp.zeros(n_slots, jnp.int32)
        self.lens = np.zeros(n_slots, np.int32)
        self.last_tok = np.zeros(n_slots, np.int32)

        # cache-pool buffers are donated: every step functionally updates
        # the pool, and without donation XLA must copy the whole pool per
        # call (the dominant cost at CPU scale)
        self._decode_fn = jax.jit(self._decode_step, donate_argnums=1)
        self._prefill_fn = jax.jit(self._prefill_one)
        self._chunk_fn = jax.jit(self._prefill_chunk_step, donate_argnums=1)
        self._write_fn = jax.jit(self._write_slot_impl, donate_argnums=0)
        self._write_masked_fn = jax.jit(self._write_slots_masked_impl,
                                        donate_argnums=0)
        self._read_fn = jax.jit(self._read_slot_impl, static_argnums=2)
        if kv_layout == "paged":
            self._decode_paged_fn = jax.jit(self._decode_step_paged,
                                            donate_argnums=1)
            self._chunk_paged_fn = jax.jit(self._prefill_chunk_step_paged,
                                           donate_argnums=1)
            self._copy_page_fn = jax.jit(self._copy_page_impl,
                                         donate_argnums=0)
        self._jit_sizes: Dict[str, int] = {}

    # ------------------------------------------------------------------ jit

    def _prefill_one(self, params, tokens, true_len, key):
        """Legacy bucketed prefill: tokens [1, bucket] (padded); returns
        (next_token [1], caches).  One jit entry PER BUCKET SIZE — the
        recompile cost this PR's chunked path removes.  `key` must be an
        explicit argument: read via closure it would be baked in as a
        trace-time constant and every stochastic sample on this path
        would reuse the same key."""
        logits, _aux, caches = api.forward(params, {"tokens": tokens},
                                           self.cfg, mode="prefill",
                                           remat="none")
        last = jnp.take_along_axis(
            logits, (true_len - 1).reshape(1, 1, 1).astype(jnp.int32),
            axis=1)[:, 0]
        tok, bad = sample_guarded(last, self.cfg.vocab_size, self.sampler,
                                  key)
        return tok, caches, bad

    def _prefill_chunk_step(self, params, caches, tokens, last_idx, key,
                            sel):
        """Batched chunk prefill over the WHOLE pool.

        tokens [n_slots, chunk] (padded per slot); last_idx [n_slots] —
        index of the final prompt token within this chunk, only
        meaningful for slots whose prefill completes this call.  Returns
        (sampled first token per slot [n_slots], new_caches).  One shape
        -> one compile, ever; non-target lanes compute too (their cache
        writes are discarded by the masked merge), the SIMT analogue of
        predicated-off lanes sharing the issue slot.
        """
        logits, _aux, new_caches = api.forward(params, {"tokens": tokens},
                                               self.cfg, mode="chunk",
                                               caches=caches, remat="none")
        last = jnp.take_along_axis(
            logits, last_idx.reshape(-1, 1, 1).astype(jnp.int32),
            axis=1)[:, 0]
        tok, bad = sample_guarded(last, self.cfg.vocab_size, self.sampler,
                                  key)
        return tok, self._masked_merge(new_caches, caches, sel), bad

    @staticmethod
    def _apply_logit_fault(last, fault_code):
        """In-jit fault injection: 0 = identity (the `where` on a traced
        scalar selects `last` verbatim — fault-free serving stays
        bit-identical), 1 = all-NaN, 2 = all-Inf.  A traced int32 arg,
        so injecting never changes the jit cache shape."""
        nanv = jnp.full_like(last, jnp.nan)
        infv = jnp.full_like(last, jnp.inf)
        return jnp.where(fault_code == 1, nanv,
                         jnp.where(fault_code == 2, infv, last))

    def _decode_step(self, params, caches, tokens, key, sel, fault_code):
        logits, _aux, new_caches = api.forward(
            params, {"tokens": tokens[:, None]}, self.cfg, mode="decode",
            caches=caches, remat="none")
        last = self._apply_logit_fault(logits[:, -1], fault_code)
        # NaN/Inf guard: rows with any non-finite logit fall back to
        # greedy over sanitized logits instead of emitting garbage
        tok, bad = sample_guarded(last, self.cfg.vocab_size, self.sampler,
                                  key)
        # jit-safe device counters (obs.registry pattern): merged into
        # the host registry once per tick after the step returns
        ctrs = obs.device_counters("sampled_tokens", "eos_sampled",
                                   "nonfinite_logit_rows")
        ctrs = obs.bump(ctrs, sampled_tokens=tok.shape[0],
                        eos_sampled=jnp.sum(tok == self.eos_id),
                        nonfinite_logit_rows=jnp.sum(bad & sel))
        ent = jnp.mean(logit_entropy(last, self.cfg.vocab_size))
        return (tok, self._masked_merge(new_caches, caches, sel), ctrs, ent,
                bad)

    # ---------------------------------------------------------- paged steps
    #
    # The paged twins of _decode_step / _prefill_chunk_step: gather the
    # contiguous [n_slots, max_len] view through the read table, run the
    # unchanged model forward on it, then scatter ONLY the dirty pages
    # back (write table + mask from PagedKV.write_plan).  Unselected
    # slots' table positions are masked off — their writes land on the
    # trash page — so the masked-merge semantics survive the page layout
    # without a separate select, and shared pages are physically
    # unreachable from the write path.  One shape -> one compile,
    # regardless of which requests hold which pages.

    def _gather_view(self, pool, lens, read_tab):
        view = kv_pool.gather_pages(pool, self._pool_ax, read_tab,
                                    self.n_slots, self._kv.pages_per_slot,
                                    self._kv.page_size)
        view["len"] = lens
        return view

    def _scatter_view(self, pool, new_caches, write_tab, wmask):
        src = {k: v for k, v in new_caches.items() if k != "len"}
        return kv_pool.scatter_pages(pool, self._pool_ax, src, write_tab,
                                     wmask, self.n_slots,
                                     self._kv.pages_per_slot,
                                     self._kv.page_size, self._kv.trash)

    def _decode_step_paged(self, params, pool, lens, read_tab, write_tab,
                           wmask, tokens, key, sel, fault_code):
        caches = self._gather_view(pool, lens, read_tab)
        logits, _aux, new_caches = api.forward(
            params, {"tokens": tokens[:, None]}, self.cfg, mode="decode",
            caches=caches, remat="none")
        last = self._apply_logit_fault(logits[:, -1], fault_code)
        tok, bad = sample_guarded(last, self.cfg.vocab_size, self.sampler,
                                  key)
        ctrs = obs.device_counters("sampled_tokens", "eos_sampled",
                                   "nonfinite_logit_rows")
        ctrs = obs.bump(ctrs, sampled_tokens=tok.shape[0],
                        eos_sampled=jnp.sum(tok == self.eos_id),
                        nonfinite_logit_rows=jnp.sum(bad & sel))
        ent = jnp.mean(logit_entropy(last, self.cfg.vocab_size))
        return (tok, self._scatter_view(pool, new_caches, write_tab, wmask),
                ctrs, ent, bad)

    def _prefill_chunk_step_paged(self, params, pool, lens, read_tab,
                                  write_tab, wmask, tokens, last_idx, key):
        caches = self._gather_view(pool, lens, read_tab)
        logits, _aux, new_caches = api.forward(params, {"tokens": tokens},
                                               self.cfg, mode="chunk",
                                               caches=caches, remat="none")
        last = jnp.take_along_axis(
            logits, last_idx.reshape(-1, 1, 1).astype(jnp.int32),
            axis=1)[:, 0]
        tok, bad = sample_guarded(last, self.cfg.vocab_size, self.sampler,
                                  key)
        return (tok, self._scatter_view(pool, new_caches, write_tab, wmask),
                bad)

    def _copy_page_impl(self, pool, src, dst):
        """One-page device copy (prefix-insert partial-page COW); src/dst
        are traced scalars so one compile covers every copy ever."""
        return kv_pool.copy_page(pool, self._pool_ax, src, dst)

    # ------------------------------------------------------------- requests

    def submit(self, prompt: Sequence[int], max_new: int = 32,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request.  `deadline_s` is a TTL from now (falls back
        to the engine's `default_deadline_s`); a request that exceeds it
        — queued or running — finishes with reason "timeout".  When the
        admission queue is bounded (`max_queue`) and full, the shed
        policy finishes a request immediately with reason "shed" instead
        of letting the queue grow without bound."""
        prompt = list(prompt)
        if not prompt or len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length must be in [1, {self.max_len - 1}]")
        if self._kv is not None:
            cap = min(len(prompt) + max_new, self.max_len)
            need = self._kv.pages_for(cap)
            if need > self._kv.pool.n_pages:
                raise ValueError(
                    f"request worst case ({need} pages of "
                    f"{self._kv.page_size}) exceeds the pool "
                    f"({self._kv.pool.n_pages} pages) — it could never "
                    "admit")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      submit_t=time.perf_counter(),
                      deadline_s=(deadline_s if deadline_s is not None
                                  else self.default_deadline_s))
        self.requests[rid] = req
        if req.deadline_s is not None:
            self._any_deadlines = True
        self.metrics.counter("serving.requests_submitted").inc()
        if (self.max_queue is not None
                and len(self.pending) >= self.max_queue):
            if self.shed_policy == "drop-oldest":
                self._finish(self.pending.popleft(), "shed")
                self.pending.append(req)
            else:                               # reject-new
                self._finish(req, "shed")
            return rid
        self.pending.append(req)
        return rid

    # -------------------------------------------------------- cache surgery

    def _write_slot_impl(self, caches, one_caches, slot):
        """Jitted body of `_write_slot`: ONE fused dynamic_update_slice
        per leaf (the eager pad + at[].set version dispatched ~30 ops and
        dominated admission latency).  Source leaves narrower than the
        pool (prefix snapshots cropped to n_tokens) are written at offset
        0 and the junk beyond them is masked by the per-slot `len` and
        overwritten in place by decode; wider leaves (legacy buckets >
        max_len) are cropped."""
        def put(pool, src, ax):
            if ax is None or pool.ndim == 0 or src.ndim == 0:
                return pool
            for sax in range(src.ndim):
                if sax != ax and src.shape[sax] > pool.shape[sax]:
                    src = jax.lax.slice_in_dim(src, 0, pool.shape[sax],
                                               axis=sax)
            starts = [jnp.int32(0)] * pool.ndim
            starts[ax] = slot
            return jax.lax.dynamic_update_slice(
                pool, src.astype(pool.dtype), tuple(starts))

        ax_tree = dict(self._slot_ax)
        ax_tree.pop("len", None)
        return jax.tree.map(put, caches, one_caches, ax_tree)

    def _write_slot(self, slot: int, one_caches, prompt_len: int):
        """Copy a prefilled (batch=1) cache into pool slot `slot`, using
        the structural slot-axis map."""
        pool_len = self.caches["len"]
        src = dict(one_caches)
        src.pop("len", None)
        tree = dict(self.caches)
        tree.pop("len")
        new = dict(self._write_fn(tree, src, jnp.int32(slot)))
        new["len"] = pool_len.at[slot].set(prompt_len)
        self.caches = new

    def _write_slots_masked_impl(self, caches, one_caches, selj):
        """Broadcast ONE batch=1 cache snapshot into every slot where
        `selj` — the coalesced prefix-copy path: an admission wave whose
        requests share a system-prompt prefix costs one pool-wide select
        instead of one copy per slot."""
        def put(pool, src, ax):
            if ax is None or pool.ndim == 0 or src.ndim == 0:
                return pool
            for sax in range(src.ndim):
                if sax != ax and src.shape[sax] > pool.shape[sax]:
                    src = jax.lax.slice_in_dim(src, 0, pool.shape[sax],
                                               axis=sax)
            pads = [(0, 0) if i == ax else
                    (0, pool.shape[i] - src.shape[i])
                    for i in range(src.ndim)]
            if any(p[1] for p in pads):
                src = jnp.pad(src, pads)
            shape = [1] * pool.ndim
            shape[ax] = self.n_slots
            return jnp.where(selj.reshape(shape), src.astype(pool.dtype),
                             pool)

        ax_tree = dict(self._slot_ax)
        ax_tree.pop("len", None)
        return jax.tree.map(put, caches, one_caches, ax_tree)

    def _write_slots_masked(self, one_caches, sel: np.ndarray):
        """Host wrapper for `_write_slots_masked_impl` (leaves the pool
        `len` untouched — the caller syncs it from `self.lens`)."""
        pool_len = self.caches["len"]
        src = dict(one_caches)
        src.pop("len", None)
        tree = dict(self.caches)
        tree.pop("len")
        new = dict(self._write_masked_fn(tree, src, jnp.asarray(sel)))
        new["len"] = pool_len
        self.caches = new

    def _read_slot_impl(self, caches, slot, n_tokens):
        """Jitted body of `_read_slot` — one compile per distinct
        `n_tokens` (bounded by max_len / chunk), slot stays traced."""
        def take(path, pool, ax):
            if ax is None or pool.ndim == 0:
                return pool
            out = jax.lax.dynamic_slice_in_dim(pool, slot, 1, axis=ax)
            names = [str(getattr(p, "key", "")) for p in path]
            last = names[-1] if names else ""
            if last in ("k", "v", "xk", "xv") or last.endswith("_scale"):
                seq_ax = ax + 1          # seq sits right of the slot axis
                if out.shape[seq_ax] > n_tokens:
                    out = jax.lax.slice_in_dim(out, 0, n_tokens,
                                               axis=seq_ax)
            return out

        ax_tree = dict(self._slot_ax)
        ax_tree.pop("len", None)
        return jax.tree_util.tree_map_with_path(take, caches, ax_tree)

    def _read_slot(self, slot: int, n_tokens: int):
        """Inverse of `_write_slot`: a batch=1 snapshot of pool slot
        `slot`, with KV sequence axes cropped to `n_tokens` (prefix-cache
        entries store only the prefix they commit to)."""
        tree = dict(self.caches)
        tree.pop("len")
        return self._read_fn(tree, jnp.int32(slot), int(n_tokens))

    def _masked_merge(self, new_caches, old_caches, sel):
        """Keep `new_caches` on slots where `sel`, `old_caches` elsewhere
        (the lane-mask merge both the decode tick and the batched chunk
        prefill use).  Called INSIDE the jitted step functions so XLA
        fuses the select into the cache write instead of dispatching one
        eager `where` per leaf per tick."""
        selj = jnp.asarray(sel)

        def keep(new, old, ax):
            if ax is None or new.ndim == 0:
                return new
            shape = [1] * new.ndim
            shape[ax] = self.n_slots
            return jnp.where(selj.reshape(shape), new, old)

        return jax.tree.map(keep, new_caches, old_caches, self._slot_ax)

    # ----------------------------------------------------------------- tick

    def _finish(self, req: Request, reason: str) -> None:
        # a request that ever sampled through the NaN/Inf guard completes
        # as "degraded" — the tokens are usable (greedy fallback) but the
        # caller must know they were produced under a fault
        if req.degraded and reason in ("eos", "max_new", "max_len"):
            reason = "degraded"
        req.done = True
        req.finish_reason = reason
        if req.slot >= 0:
            if self._kv is not None:
                # drop every page reference the slot holds (shared prefix
                # pins, private pages, unresolved pending-COW copies);
                # pages whose refcount hits zero return to the free list
                self._kv.release_slot(req.slot)
            self.sched.retire(req.slot)
            # drop the engine's slot->request pin: retired requests must
            # not stay reachable from the engine for its whole lifetime
            self._slot_req.pop(req.slot, None)
            self._prefill_pos.pop(req.slot, None)
            self._chunk_hashes.pop(req.slot, None)
        self.metrics.counter("serving.requests_completed").inc()
        self.metrics.counter(f"serving.requests_completed.{reason}").inc()
        now = time.perf_counter()
        if req.submit_t:
            self.metrics.histogram("serving.request_latency_s").observe(
                now - req.submit_t)
        flight.record("serving.finish", rid=req.rid, reason=reason,
                      out_tokens=len(req.out), degraded=req.degraded)
        if obs.tracer.enabled and req.submit_t:
            # request-track epilogue: the decode phase (first -> last
            # token) and the whole-request envelope carrying the finish
            # reason, both on this rid's Perfetto track
            if req.first_tok_t and req.last_tok_t > req.first_tok_t:
                obs.tracer.complete(
                    "decode", req.first_tok_t, req.last_tok_t,
                    pid=_REQ_TRACK_PID, tid=req.rid,
                    tokens=max(len(req.out) - 1, 0))
            obs.tracer.complete("request", req.submit_t, now,
                                pid=_REQ_TRACK_PID, tid=req.rid,
                                rid=req.rid, reason=reason,
                                out_tokens=len(req.out))

    def _enforce_deadlines(self) -> None:
        """Time out queued and running requests past their TTL.  Queued
        expirations leave the deque; running ones retire their slot (the
        warp analogue: a lane that exceeds its budget is masked off so
        the rest of the machine keeps issuing)."""
        if not self._any_deadlines:
            return
        now = time.perf_counter()

        def expired(r: Request) -> bool:
            return (r.deadline_s is not None
                    and now - r.submit_t > r.deadline_s)

        if any(expired(r) for r in self.pending):
            keep: Deque[Request] = deque()
            for r in self.pending:
                if expired(r):
                    self._finish(r, "timeout")
                else:
                    keep.append(r)
            self.pending = keep
        for req in list(self._slot_req.values()):
            if expired(req):
                self._finish(req, "timeout")

    # ------------------------------------------------------- paged admission

    def _on_prefix_evict(self, entry: PrefixEntry) -> None:
        """PrefixCache eviction hook (paged mode): release the entry's
        page references.  Pages shared with live slots survive (refcount
        > 0); unshared ones return to the free list."""
        if entry.pages:
            freed = self._kv.pool.release(entry.pages)
            self.metrics.counter("serving.kv.evicted_pages").inc(
                len(entry.pages))
            flight.record("kv.evict", pages=len(entry.pages), freed=freed,
                          n_tokens=entry.n_tokens)
        self.metrics.counter("serving.prefix_cache.evictions").inc()

    def _admit_paged(self, req: Request) -> int:
        """Paged admission: admit only if a slot is free AND the pool
        covers the request's worst case (`ceil(min(prompt + max_new,
        max_len) / page_size)` pages, minus full pages pinned from a
        prefix hit — a shared partial page still bills one fresh page
        for its eager COW copy).  Pool pressure evicts LRU prefix
        entries before giving up; a request that still doesn't fit stays
        queued (`kv.oom` flight event + `serving.kv.admit_blocked`).

        The prefix match happens HERE, not in a post-admission wave: the
        hit pins the entry's pages (refcount bump, O(1) per hit) instead
        of copying the prefix into the slot, and the pinned pages must
        survive any pressure eviction of their own entry."""
        if not bool((~self.sched.active).any()):
            return -1
        kv, m = self._kv, self.metrics
        cap = min(len(req.prompt) + req.max_new, self.max_len)
        matched, entry, hashes = 0, None, []
        if self.prefix is not None:
            matched, entry, hashes = self.prefix.match(req.prompt)
        shared = list(entry.pages) if (matched and entry.pages) else []
        if not shared:
            matched = 0
        else:
            kv.pool.share(shared)        # pin before any pressure eviction
        need = kv.fresh_pages_needed(cap, matched)
        while (kv.pool.free_pages < need and self.prefix is not None
               and len(self.prefix)):
            self.prefix.evict_lru()      # releases pages via _on_prefix_evict
        if kv.pool.free_pages < need:
            if shared:
                kv.pool.release(shared)
            m.counter("serving.kv.admit_blocked").inc()
            if self._last_oom_rid != req.rid:   # one flight event per
                self._last_oom_rid = req.rid    # blocked request, not tick
                flight.record("kv.oom", rid=req.rid, need_pages=need,
                              free_pages=kv.pool.free_pages)
            return -1
        slot = self.sched.admit()
        assert slot >= 0
        kv.bind(slot, cap, matched, shared)
        self._chunk_hashes[slot] = hashes
        if self.prefix is not None:
            n_chunks = matched // self.chunk
            m.counter("serving.prefix_cache.hits").inc(n_chunks)
            m.counter("serving.prefix_cache.misses").inc(
                len(hashes) - n_chunks)
            m.counter("serving.prefix_cache.hit_tokens").inc(matched)
            if obs.tracer.enabled:
                obs.tracer.instant(
                    "prefix_hit" if matched else "prefix_miss",
                    pid=_REQ_TRACK_PID, tid=req.rid, matched_tokens=matched)
        if shared:
            m.counter("serving.kv.pages_shared").inc(len(shared))
        self.lens[slot] = matched
        self._prefill_pos[slot] = matched
        return slot

    def _commit_cow(self, commits) -> None:
        """Apply the tick's COW resolutions after the device step: point
        tables at the freshly-written copies, drop the shared-page refs,
        and account the split (one page written = the whole per-hit copy
        cost; full shared pages are never copied)."""
        if not commits:
            return
        self._kv.commit(commits)
        m = self.metrics
        m.counter("serving.kv.cow_splits").inc(len(commits))
        m.counter("serving.kv.pages_copied").inc(len(commits))
        for c in commits:
            flight.record("kv.cow", slot=c.slot, pos=c.pos,
                          old_page=c.old_page, new_page=c.new_page)

    def _begin_prefill_batch(self, admitted) -> None:
        """Admission-time prefix-cache lookup for a whole admission wave:
        copy the longest cached KV prefix into each slot and start its
        chunk cursor past it.  Slots that matched the SAME prefix entry
        (the shared-system-prompt case) are written in one coalesced
        masked broadcast instead of one copy per slot."""
        m = self.metrics
        groups: Dict[int, list] = {}    # id(entry) -> [entry, [slots]]
        for slot, req in admitted:
            matched = 0
            if self.prefix is not None:
                matched, entry, hashes = self.prefix.match(req.prompt)
                self._chunk_hashes[slot] = hashes
                n_chunks = matched // self.chunk
                m.counter("serving.prefix_cache.hits").inc(n_chunks)
                m.counter("serving.prefix_cache.misses").inc(
                    len(hashes) - n_chunks)
                m.counter("serving.prefix_cache.hit_tokens").inc(matched)
                if obs.tracer.enabled:
                    # hit/miss marker on the request's own track, right
                    # where its prefill timeline begins
                    obs.tracer.instant(
                        "prefix_hit" if matched else "prefix_miss",
                        pid=_REQ_TRACK_PID, tid=req.rid,
                        matched_tokens=matched)
            if matched:
                groups.setdefault(id(entry), [entry, []])[1].append(slot)
            self.lens[slot] = matched
            self._prefill_pos[slot] = matched
        for entry, slots in groups.values():
            sel = np.zeros(self.n_slots, bool)
            sel[slots] = True
            self._write_slots_masked(entry.caches, sel)
        # ONE authoritative host->device len write per wave: matched
        # slots start past their prefix, fresh (possibly recycled) slots
        # reset to 0
        self.caches["len"] = jnp.asarray(self.lens)

    def _insert_prefix_entries(self, slot: int, req: Request) -> None:
        """After a slot finishes prefilling, snapshot the DEEPEST
        full-chunk boundary of its prompt into the prefix cache.  A
        chain hash commits to its entire prefix and match() scans
        deepest-first, so intermediate boundaries need no entries of
        their own — storing them would multiply snapshot memory and
        admission-copy work for no extra match depth."""
        if self.prefix is None:
            return
        hashes = self._chunk_hashes.pop(slot, [])
        if not hashes:
            return
        m = self.metrics
        hkey = hashes[-1]
        n = len(hashes) * self.chunk
        if hkey in self.prefix:
            self.prefix.insert(hkey, None, n)       # recency refresh only
        elif self._kv is not None:
            # paged insert: the entry takes references on the slot's full
            # pages (no copy); a trailing partial page is device-copied
            # into a fresh page iff the donor will still write inside it.
            # Under pool pressure the copy may be skipped — the entry is
            # then truncated to its full pages.
            kv = self._kv
            if kv.pool.free_pages == 0 and n % kv.page_size:
                self.prefix.evict_lru()  # make room for the partial copy
            pages, copy, n_stored = kv.entry_pages(
                slot, n, next_write_pos=int(self.lens[slot]))
            if pages:
                if copy is not None:
                    self.caches = self._copy_page_fn(
                        self.caches, jnp.int32(copy[0]), jnp.int32(copy[1]))
                    m.counter("serving.kv.pages_copied").inc()
                # evictions are counted by _on_prefix_evict
                self.prefix.insert(hkey, None, n_stored, pages=pages)
                m.counter("serving.prefix_cache.inserts").inc()
        else:
            ev = self.prefix.insert(hkey, self._read_slot(slot, n), n)
            m.counter("serving.prefix_cache.inserts").inc()
            m.counter("serving.prefix_cache.evictions").inc(ev)
        m.gauge("serving.prefix_cache.size").set(len(self.prefix))

    def _finish_slot_prefill(self, slot: int, req: Request, tok: int) -> None:
        """Shared prefill epilogue: record TTFT, seed decode state."""
        m = self.metrics
        now = time.perf_counter()
        req.first_tok_t = req.last_tok_t = now
        m.histogram("serving.ttft_s").observe(now - req.submit_t)
        flight.record("serving.first_token", rid=req.rid, slot=slot,
                      ttft_s=round(now - req.submit_t, 6))
        if obs.tracer.enabled and req.admit_t:
            # the whole prefill phase (admission -> first token) on this
            # rid's track; the prefill_chunk intervals nest inside it
            obs.tracer.complete("prefill", req.admit_t, now,
                                pid=_REQ_TRACK_PID, tid=req.rid,
                                prompt_tokens=len(req.prompt))
        m.counter("serving.prefills").inc()
        m.counter("serving.prompt_tokens").inc(len(req.prompt))
        m.counter("serving.tokens").inc()
        self.last_tok[slot] = tok
        req.out.append(tok)
        self.lens[slot] = len(req.prompt)
        self.sched.prefill_done(slot)
        self._insert_prefix_entries(slot, req)

    def _prefill_tick_chunked(self) -> None:
        """Advance EVERY stalled slot by one chunk in one batched call."""
        targets = self.sched.prefill_targets()
        if len(targets) == 0:
            return
        m = self.metrics
        C = self.chunk
        toks = np.zeros((self.n_slots, C), np.int32)
        last_idx = np.zeros(self.n_slots, np.int32)
        seg_len = {}
        for slot in targets:
            slot = int(slot)
            req = self._slot_req[slot]
            pos = self._prefill_pos[slot]
            seg = req.prompt[pos:pos + C]
            toks[slot, :len(seg)] = seg
            last_idx[slot] = len(seg) - 1
            seg_len[slot] = len(seg)
        sel = np.zeros(self.n_slots, bool)
        sel[targets] = True
        self._key, k = jax.random.split(self._key)
        t_chunk0 = time.perf_counter()
        with obs.trace.span("prefill_chunk", n=int(len(targets))):
            if self._kv is not None:
                writes = {s: (self._prefill_pos[s],
                              self._prefill_pos[s] + L)
                          for s, L in seg_len.items()}
                rtab, wtab, wmask, commits = self._kv.write_plan(writes)
                tok, self.caches, bad = self._chunk_paged_fn(
                    self.params, self.caches, jnp.asarray(self.lens),
                    jnp.asarray(rtab), jnp.asarray(wtab),
                    jnp.asarray(wmask), jnp.asarray(toks),
                    jnp.asarray(last_idx), k)
                self._commit_cow(commits)
            else:
                tok, self.caches, bad = self._chunk_fn(
                    self.params, self.caches, jnp.asarray(toks),
                    jnp.asarray(last_idx), k, jnp.asarray(sel))
            tok_np = np.asarray(tok)
            bad_np = np.asarray(bad)
        if obs.tracer.enabled:
            # mirror the batched chunk call onto every participating
            # request's track — the shared interval shows exactly which
            # requests rode the same batched prefill call
            t_chunk1 = time.perf_counter()
            for slot in targets:
                slot = int(slot)
                obs.tracer.complete(
                    "prefill_chunk", t_chunk0, t_chunk1,
                    pid=_REQ_TRACK_PID, tid=self._slot_req[slot].rid,
                    pos=self._prefill_pos[slot], tokens=seg_len[slot])
        m.counter("serving.prefill_chunk_calls").inc()
        m.counter("serving.prefill_chunks").inc(int(len(targets)))
        m.histogram("serving.prefill_batch_width").observe(len(targets))
        for slot in targets:
            slot = int(slot)
            req = self._slot_req[slot]
            pos_new = self._prefill_pos[slot] + seg_len[slot]
            self._prefill_pos[slot] = pos_new
            self.lens[slot] = pos_new
            self.sched.prefill_step(slot)
            if pos_new >= len(req.prompt):
                if bool(bad_np[slot]):
                    req.degraded = True
                    m.counter("serving.degraded_samples").inc()
                self._finish_slot_prefill(slot, req, int(tok_np[slot]))
        if self._kv is None:
            # one authoritative host->device len write per tick: targets
            # got their cursors advanced, finished slots their true prompt
            # length (the paged pool has no len leaf — self.lens is a
            # traced argument of every paged step instead)
            self.caches["len"] = jnp.asarray(self.lens)

    def _prefill_tick_legacy(self) -> None:
        """Pre-PR path: one [1, bucket] forward per stalled slot, with a
        per-bucket jit entry.  Kept as the fallback for families without
        chunk-append caches and as the serving benchmark's baseline."""
        m = self.metrics
        for slot in self.sched.prefill_targets():
            slot = int(slot)
            req = self._slot_req[slot]
            L = len(req.prompt)
            buck = self.bucket
            while buck < L:
                buck *= 2
            toks = np.zeros((1, buck), np.int32)
            toks[0, :L] = req.prompt
            self._key, k = jax.random.split(self._key)
            with obs.trace.span("prefill", rid=req.rid, len=L, bucket=buck):
                tok, one, bad = self._prefill_fn(self.params,
                                                 jnp.asarray(toks),
                                                 jnp.asarray([L], jnp.int32),
                                                 k)
                self._write_slot(slot, one, L)
                t = int(tok[0])
            if bool(np.asarray(bad)[0]):
                req.degraded = True
                m.counter("serving.degraded_samples").inc()
            self.sched.prefill_step(slot)
            self._finish_slot_prefill(slot, req, t)

    def step(self) -> int:
        """One engine tick with a watchdog: transient failures (the
        injectable `TransientFault` class — flaky collectives, preempted
        devices) retry with capped exponential backoff up to
        `step_retries` times before propagating.  The injected check
        fires BEFORE any tick mutation, so a retried tick replays
        cleanly.  Slow ticks (wall time over `tick_budget_s`) are
        counted but never retried — latency is handled by deadlines, not
        by re-running work."""
        m = self.metrics
        attempt = 0
        while True:
            self.liveness.beat()
            t_tick = time.perf_counter()
            try:
                if self.faults is not None:
                    self.faults.check_raise("serving.step")
                produced = self._step_inner()
            except TransientFault as e:
                m.counter("serving.watchdog.transient_faults").inc()
                if attempt >= self.step_retries:
                    m.counter("serving.watchdog.gave_up").inc()
                    flight.record("serving.watchdog.gave_up",
                                  attempt=attempt, exc=str(e))
                    raise
                delay = min(self.retry_base_s * (2 ** attempt),
                            self.retry_max_s)
                m.counter("serving.watchdog.retries").inc()
                flight.record("serving.watchdog.retry", attempt=attempt,
                              delay_s=delay, exc=str(e))
                time.sleep(delay)
                attempt += 1
                continue
            dt = time.perf_counter() - t_tick
            m.histogram("serving.tick_s").observe(dt)
            if self.tick_budget_s is not None and dt > self.tick_budget_s:
                m.counter("serving.watchdog.slow_ticks").inc()
                flight.record("serving.watchdog.slow_tick", dt_s=round(dt, 6),
                              budget_s=self.tick_budget_s)
            return produced

    def _step_inner(self) -> int:
        """One engine tick: time out -> admit -> prefill -> decode.
        Returns number of *decode* tokens produced this tick.

        Token-count contract: `max_new` is the number of *decode* tokens
        generated after prefill.  The prefill pass itself samples one
        token (the first entry of `req.out`), so a request that never
        hits EOS/max_len finishes with ``len(req.out) == max_new + 1``.
        (Earlier revisions compared ``len(req.out) >= max_new`` which,
        because the prefill token already counts toward ``req.out``,
        ended one decode token early.)
        """
        m = self.metrics
        # 0. deadline sweep: expired requests (queued or running) finish
        # as "timeout" and free their slots before admission
        self._enforce_deadlines()
        # 1. admission (slots are warps; wspawn) — batched, so prefix
        # copies for a wave sharing one entry coalesce into one write
        admitted = []
        while self.pending:
            if self._kv is not None:
                # paged admission peeks: match + pin + allocate first,
                # claim the slot only once the pool covers the request
                slot = self._admit_paged(self.pending[0])
            else:
                slot = self.sched.admit()
            if slot < 0:
                break
            req = self.pending.popleft()
            req.slot = slot
            req.admit_t = time.perf_counter()
            self._slot_req[slot] = req
            admitted.append((slot, req))
            flight.record("serving.admit", rid=req.rid, slot=slot,
                          prompt_tokens=len(req.prompt))
            if obs.tracer.enabled:
                # open this rid's Perfetto track: name it and lay the
                # queue-wait interval (submit -> admit) as its first span
                obs.tracer.thread_name(_REQ_TRACK_PID, req.rid,
                                       f"req {req.rid}")
                obs.tracer.complete("queue_wait", req.submit_t, req.admit_t,
                                    pid=_REQ_TRACK_PID, tid=req.rid,
                                    slot=slot)
        if admitted and self._kv is None:
            self._begin_prefill_batch(admitted)
        if self._kv is not None:
            free = self._kv.pool.free_pages
            m.gauge("serving.kv.free_pages").set(free)
            m.gauge("serving.kv.pool_occupancy").set(
                1.0 - free / self._kv.pool.n_pages)
        m.gauge("serving.queue_depth").set(len(self.pending))
        m.gauge("serving.slot_occupancy").set(
            float(self.sched.active.sum()) / self.n_slots)

        # 2. prefill stalled slots (memory-wait analogue): chunked slots
        # stay stalled-but-progressing across ticks; legacy slots fill in
        # one blocking call each
        if self.faults is not None:
            d = self.faults.delay_s("serving.prefill")
            if d:
                m.counter("serving.faults.delayed_prefill_ticks").inc()
                time.sleep(d)
        if self.prefill_mode == "chunked":
            self._prefill_tick_chunked()
        else:
            self._prefill_tick_legacy()
        self._note_recompiles()

        # 3. decode tick over selected slots
        picked = self.sched.next_batch(self.decode_width)
        if not picked:
            return 0
        sel = np.zeros(self.n_slots, bool)
        sel[picked] = True
        # decode-batch efficiency: selected / total lanes — every slot
        # decodes (masked), only `picked` keep their result, exactly the
        # SIMT lane-utilization analogue
        m.counter("serving.decode_ticks").inc()
        m.counter("serving.decode_lanes_selected").inc(len(picked))
        m.counter("serving.decode_lanes_total").inc(self.n_slots)
        m.gauge("serving.decode_batch_efficiency").set(
            len(picked) / self.n_slots)
        # lanes not selected decode too (masked); their state is restored
        fault_code = 0
        if self.faults is not None:
            d = self.faults.delay_s("serving.decode")
            if d:
                m.counter("serving.faults.delayed_decode_ticks").inc()
                time.sleep(d)
            fault_code = self.faults.logit_fault_code("serving.logits")
        self._key, k = jax.random.split(self._key)
        toks = jnp.asarray(self.last_tok)
        with obs.trace.span("decode_tick", n=len(picked)):
            if self._kv is not None:
                writes = {int(s): (int(self.lens[s]), int(self.lens[s]) + 1)
                          for s in picked}
                rtab, wtab, wmask, commits = self._kv.write_plan(writes)
                new_tok, self.caches, dev_ctrs, ent, bad = \
                    self._decode_paged_fn(
                        self.params, self.caches, jnp.asarray(self.lens),
                        jnp.asarray(rtab), jnp.asarray(wtab),
                        jnp.asarray(wmask), toks, k, jnp.asarray(sel),
                        jnp.int32(fault_code))
                self._commit_cow(commits)
            else:
                new_tok, self.caches, dev_ctrs, ent, bad = self._decode_fn(
                    self.params, self.caches, toks, k, jnp.asarray(sel),
                    jnp.int32(fault_code))
            toks_np = np.asarray(new_tok)
            bad_np = np.asarray(bad)
        obs.merge_device(m, dev_ctrs, prefix="serving.decode.")
        ent = float(ent)
        if np.isfinite(ent):     # a faulted tick's entropy is NaN/Inf —
            # keep it out of the histogram so healthy-traffic stats stay
            # meaningful; the fault itself is counted via
            # serving.decode.nonfinite_logit_rows
            m.histogram("serving.decode.logit_entropy").observe(ent)
        self._note_recompiles()

        produced = 0
        now = time.perf_counter()
        for slot in picked:
            req = self._slot_req[slot]
            t = int(toks_np[slot])
            if bool(bad_np[slot]):
                req.degraded = True
                m.counter("serving.degraded_samples").inc()
            req.out.append(t)
            if req.last_tok_t:
                m.histogram("serving.itl_s").observe(now - req.last_tok_t)
            req.last_tok_t = now
            self.last_tok[slot] = t
            self.lens[slot] += 1
            produced += 1
            if t == self.eos_id:
                self._finish(req, "eos")
            elif len(req.out) - 1 >= req.max_new:     # prefill tok excluded
                self._finish(req, "max_new")
            elif self.lens[slot] >= self.max_len - 1:
                self._finish(req, "max_len")
        m.counter("serving.tokens").inc(produced)
        m.gauge("serving.tokens_per_s").set(
            m.counter("serving.tokens").value
            / max(time.perf_counter() - self._t_start, 1e-9))
        return produced

    def _note_recompiles(self) -> None:
        """Export jit-cache growth as `serving.recompiles.*` counters —
        the chunked path's whole point is that `prefill_chunk` stays at
        1 forever while legacy `prefill` grows per bucket."""
        fns = [("prefill", self._prefill_fn),
               ("prefill_chunk", self._chunk_fn),
               ("decode", self._decode_fn)]
        if self._kv is not None:
            fns += [("prefill_chunk_paged", self._chunk_paged_fn),
                    ("decode_paged", self._decode_paged_fn)]
        for name, fn in fns:
            try:
                n = int(fn._cache_size())
            except Exception:
                continue
            prev = self._jit_sizes.get(name, 0)
            if n > prev:
                self.metrics.counter(f"serving.recompiles.{name}").inc(
                    n - prev)
                self._jit_sizes[name] = n

    def run(self, max_ticks: int = 1000) -> None:
        """Tick until every request has finished; raises RuntimeError if
        `max_ticks` run out with requests still queued or in a slot."""
        for _ in range(max_ticks):
            if not (self.pending or self.sched.active.any()):
                return
            self.step()
        if self.pending or self.sched.active.any():
            raise RuntimeError(
                f"Engine.run: {max_ticks} ticks ran out with "
                f"{len(self.pending)} requests queued and "
                f"{int(self.sched.active.sum())} in slots")

    def results(self) -> Dict[int, List[int]]:
        return {rid: r.out for rid, r in self.requests.items()}

    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON-serializable summary of every serving instrument."""
        if self.prefix is not None:
            self.metrics.gauge("serving.prefix_cache.size").set(
                len(self.prefix))
        if self._kv is not None:
            free = self._kv.pool.free_pages
            self.metrics.gauge("serving.kv.free_pages").set(free)
            self.metrics.gauge("serving.kv.pool_occupancy").set(
                1.0 - free / self._kv.pool.n_pages)
        return self.metrics.snapshot()

    def debug_requests(self, max_done: int = 32) -> List[Dict[str, Any]]:
        """JSON-serializable state of every request the engine knows:
        in-flight requests (queued / prefill / decode) in full, finished
        ones capped to the most recent `max_done` so a long-lived server's
        `/debug/requests` response stays bounded."""
        now = time.perf_counter()
        rows: List[Dict[str, Any]] = []
        done_rows: List[Dict[str, Any]] = []
        for rid, req in self.requests.items():
            if req.done:
                state = "done"
            elif req.slot < 0:
                state = "queued"
            elif req.slot in self._prefill_pos \
                    and self._prefill_pos[req.slot] < len(req.prompt) \
                    or not req.first_tok_t:
                state = "prefill"
            else:
                state = "decode"
            row = {"rid": rid, "state": state, "slot": req.slot,
                   "prompt_tokens": len(req.prompt),
                   "out_tokens": len(req.out),
                   "max_new": req.max_new,
                   "finish_reason": req.finish_reason or None,
                   "age_s": round(now - req.submit_t, 4)
                   if req.submit_t else None,
                   "deadline_s": req.deadline_s,
                   "degraded": req.degraded}
            (done_rows if req.done else rows).append(row)
        return rows + done_rows[-max_done:]
