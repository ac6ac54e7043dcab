"""The lane mask inside the per-layer KV write: `cache_update` and
`chunk_cache_update` with `lanes`, and the contiguous decode and chunk
step programs that pass the mask down, checked bit for bit against the
whole-cache `where(sel, new, old)` they replace."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import api
from repro.models.attention import cache_update, chunk_cache_update
from repro.serving.engine import Engine

B, S, KV, D = 4, 16, 2, 8


def _vmapped_write(cache, new, idx):
    """The unmasked per-slot write as it was before `lanes` existed."""
    return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n.astype(c.dtype), i, axis=0))(cache, new, idx)


def _cache_update_before(k_cache, v_cache, k_new, v_new, cache_len,
                         window=None):
    Bn, Smax = k_cache.shape[0], k_cache.shape[1]
    lens = jnp.broadcast_to(cache_len, (Bn,))
    idx = lens % Smax if (window is not None and Smax == window) else lens
    return (_vmapped_write(k_cache, k_new, idx),
            _vmapped_write(v_cache, v_new, idx))


def _chunk_cache_update_before(k_cache, v_cache, k_new, v_new, cache_len):
    lens = jnp.broadcast_to(cache_len, (k_cache.shape[0],))
    return (_vmapped_write(k_cache, k_new, lens),
            _vmapped_write(v_cache, v_new, lens))


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("kind,n,window,lens", [
    ("decode", 1, None, [0, 5, 15, 9]),
    ("decode", 1, S, [3, 17, 31, 16]),          # ring: index is len % S
    ("chunk", 4, None, [0, 12, 14, 3]),         # 14 + 4 > S: start clamps
])
def test_masked_update_keeps_unselected_lanes(kind, n, window, lens):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    kc, vc = _rand(ks[0], (B, S, KV, D)), _rand(ks[1], (B, S, KV, D))
    kn, vn = _rand(ks[2], (B, n, KV, D)), _rand(ks[3], (B, n, KV, D))
    lens = jnp.asarray(lens, jnp.int32)
    lanes = jnp.asarray([True, False, False, True])
    if kind == "decode":
        got = cache_update(kc, vc, kn, vn, lens, window=window, lanes=lanes)
        want = cache_update(kc, vc, kn, vn, lens, window=window)
    else:
        got = chunk_cache_update(kc, vc, kn, vn, lens, lanes=lanes)
        want = chunk_cache_update(kc, vc, kn, vn, lens)
    for g, w, old in zip(got, want, (kc, vc)):
        g, w, old = map(np.asarray, (g, w, old))
        np.testing.assert_array_equal(g[[0, 3]], w[[0, 3]])
        np.testing.assert_array_equal(g[[1, 2]], old[[1, 2]])
        assert not np.array_equal(w[[1, 2]], old[[1, 2]])


def test_masked_update_of_int8_payload_and_scales():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q8 = lambda k, shape: jax.random.randint(k, shape, -127, 128, jnp.int8)
    kc, vc = q8(ks[0], (B, S, KV, D)), q8(ks[1], (B, S, KV, D))
    sc = _rand(ks[2], (B, S, KV), jnp.float16)
    kn, vn = q8(ks[3], (B, 1, KV, D)), q8(ks[2], (B, 1, KV, D))
    sn = _rand(ks[3], (B, 1, KV), jnp.float16)
    lens = jnp.asarray([2, 7, 0, 15], jnp.int32)
    lanes = jnp.asarray([False, True, True, False])
    for cache, new in ((kc, kn), (vc, vn), (sc, sn)):
        got, _ = cache_update(cache, cache, new, new, lens, lanes=lanes)
        want, _ = cache_update(cache, cache, new, new, lens)
        got, want, old = map(np.asarray, (got, want, cache))
        assert got.dtype == old.dtype
        np.testing.assert_array_equal(got[[1, 2]], want[[1, 2]])
        np.testing.assert_array_equal(got[[0, 3]], old[[0, 3]])


@pytest.mark.parametrize("kind,window", [
    ("decode", None), ("decode", S), ("chunk", None)])
def test_no_lanes_lowers_to_the_unmasked_program(kind, window):
    n = 1 if kind == "decode" else 4
    cache = jax.ShapeDtypeStruct((B, S, KV, D), jnp.bfloat16)
    new = jax.ShapeDtypeStruct((B, n, KV, D), jnp.float32)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    if kind == "decode":
        now = lambda *a: cache_update(*a, window=window, lanes=None)
        before = lambda *a: _cache_update_before(*a, window=window)
    else:
        now = lambda *a: chunk_cache_update(*a, lanes=None)
        before = _chunk_cache_update_before

    def text(fn):
        def write(kc, vc, kn, vn, ln):
            return fn(kc, vc, kn, vn, ln)
        return jax.jit(write).lower(cache, cache, new, new, lens).as_text()
    assert text(now) == text(before)


# ------------------------------------------------------------ step programs

CFG = reduced_config("phi3-mini-3.8b").replace(num_layers=2)
PARAMS = api.build_params(jax.random.PRNGKey(0), CFG)
N_SLOTS, MAX_LEN, CHUNK = 4, 32, 8

# (step, case): lens, sel
CASES = {
    ("decode", "random"): None,
    ("chunk", "random"): None,
    ("decode", "last_row"): ([MAX_LEN - 1, MAX_LEN - 1, 5, 9],
                             [True, False, True, False]),
    # unselected lanes whose chunk passes max_len: the write start clamps
    ("chunk", "clamped"): ([3, MAX_LEN - 3, 12, MAX_LEN - CHUNK + 1],
                           [True, False, True, False]),
    ("decode", "ring"): ([20, 7, 31, 16], [False, True, True, False]),
    ("decode", "int8"): ([0, 13, 30, 6], [True, True, False, False]),
}


def _filled(caches, key):
    """Random contents for every cache leaf (zeros would hide a lane
    written with the wrong rows)."""
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(key, len(leaves))

    def fill(k, x):
        if jnp.issubdtype(x.dtype, jnp.integer):
            return jax.random.randint(k, x.shape, -127, 128, x.dtype)
        return _rand(k, x.shape, x.dtype)
    return jax.tree.unflatten(tree, [fill(k, x) for k, x in
                                     zip(keys, leaves)])


def _whole_cache_where(new, old, sel):
    """The merge the step programs ran before: every leaf's slot axis
    (1 for stacked per-layer leaves, 0 for `len`) selected by `sel`."""
    def keep(n, o):
        ax = 0 if n.ndim == 1 else 1
        shape = [1] * n.ndim
        shape[ax] = sel.shape[0]
        return jnp.where(sel.reshape(shape), n, o)
    return jax.tree.map(keep, new, old)


@pytest.mark.parametrize("step,case", list(CASES))
def test_step_equals_whole_cache_where(step, case):
    cfg = CFG.replace(sliding_window=16) if case == "ring" else CFG
    eng = Engine(cfg, PARAMS, n_slots=N_SLOTS, max_len=MAX_LEN,
                 prefill_chunk=CHUNK, eos_id=-1)
    assert eng._lane_write
    rng = np.random.default_rng(sum(map(ord, step + case)))
    if CASES[step, case] is None:
        hi = MAX_LEN - (CHUNK if step == "chunk" else 1)
        lens = rng.integers(0, hi, N_SLOTS)
        sel = np.zeros(N_SLOTS, bool)
        sel[rng.permutation(N_SLOTS)[:2]] = True
    else:
        lens, sel = map(np.asarray, CASES[step, case])
    caches = (api.init_caches(cfg, N_SLOTS, MAX_LEN, kv_quant=True)
              if case == "int8" else eng.caches)
    caches = _filled(caches, jax.random.PRNGKey(11))
    caches["len"] = jnp.asarray(lens, jnp.int32)
    sel = jnp.asarray(sel)
    key = jax.random.PRNGKey(5)
    if step == "decode":
        tokens = jnp.asarray(rng.integers(2, 200, N_SLOTS), jnp.int32)
        args = (tokens, key, sel, jnp.int32(0))
        step_fn, mode, fwd_tokens = eng._decode_fn, "decode", tokens[:, None]
    else:
        tokens = jnp.asarray(rng.integers(2, 200, (N_SLOTS, CHUNK)),
                             jnp.int32)
        last_idx = jnp.full(N_SLOTS, CHUNK - 1, jnp.int32)
        args = (tokens, last_idx, key, sel)
        step_fn, mode, fwd_tokens = eng._chunk_fn, "chunk", tokens

    @jax.jit
    def before(caches):
        logits, _aux, new = api.forward(PARAMS, {"tokens": fwd_tokens}, cfg,
                                        mode=mode, caches=caches,
                                        remat="none")
        tok, _bad = (eng._sample_decode(logits, key, jnp.int32(0))
                     if step == "decode" else
                     eng._sample_last(logits, last_idx, key))
        return tok, _whole_cache_where(new, caches, sel)

    want_tok, want = before(caches)
    copy = jax.tree.map(jnp.copy, caches)       # the step donates its cache
    got_tok, got = step_fn(PARAMS, copy, *args)[:2]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    picked = np.asarray(sel)
    np.testing.assert_array_equal(np.asarray(got_tok)[picked],
                                  np.asarray(want_tok)[picked])


def test_kept_lanes_counter_sums_decode_and_chunk_calls():
    eng = Engine(CFG, PARAMS, n_slots=N_SLOTS, max_len=MAX_LEN,
                 prefill_chunk=CHUNK, eos_id=-1)
    kept = {"_decode_fn": [], "_chunk_fn": []}
    # the lane mask is argument 4 of the decode program, 5 of the chunk
    for attr, sel_arg in (("_decode_fn", 4), ("_chunk_fn", 5)):
        def counted(*a, _fn=getattr(eng, attr), _attr=attr, _i=sel_arg):
            kept[_attr].append(int(N_SLOTS - np.asarray(a[_i]).sum()))
            return _fn(*a)
        setattr(eng, attr, counted)
    for p in ([5, 9, 2], [7, 1] * 6, [3]):
        eng.submit(p, max_new=3)
    eng.run()
    assert kept["_decode_fn"] and kept["_chunk_fn"]
    total = sum(map(sum, kept.values()))
    snap = eng.metrics_snapshot()
    assert snap["serving.kv_write.kept_lanes"]["value"] == total > 0
