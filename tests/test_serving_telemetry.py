"""The serving engine's telemetry: phase spans on the profiler's clock
inside one `serving.tick` step annotation, one device-to-host read per
step program call, the decode counters counted on the host, and the
module names the chip benchmark's readers match."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import trace_reduce
from repro.configs import reduced_config
from repro.faults import Fault, FaultInjector, FaultPlan
from repro.models import api
from repro.serving.engine import Engine

CFG = reduced_config("phi3-mini-3.8b").replace(num_layers=2)
PARAMS = api.build_params(jax.random.PRNGKey(0), CFG)
CFG32 = CFG.replace(dtype="float32")
PARAMS32 = api.build_params(jax.random.PRNGKey(0), CFG32)
CONTRACT = [[5, 9, 2], [7, 1], [3, 3, 3, 3], [11, 4, 6], [8], [2, 9]]
LAYOUTS = ["contiguous", "paged"]


def _engine(layout, cfg=CFG, params=PARAMS, **kw):
    return Engine(cfg, params, max_len=64, prompt_bucket=8, prefill_chunk=8,
                  kv_layout=layout, kv_page_size=8, **kw)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_tick_phases_nest_in_the_step_annotation(tmp_path, layout):
    eng = _engine(layout, n_slots=2, eos_id=-1)
    eng.submit([5, 9, 2], max_new=2)
    eng.run()                                     # compile outside
    eng.submit([7, 1, 4], max_new=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    host = trace_reduce.load(str(tmp_path)).host
    lines = [evs for evs in host.values()
             if any(e.name == "serving.tick" for e in evs)]
    assert len(lines) == 1                        # one thread
    evs = lines[0]
    ticks = [e for e in evs if e.name == "serving.tick"]
    # the first tick prefills and decodes, the second decodes
    assert len(ticks) == 2
    for n, tick in enumerate(ticks):
        inner = [e for e in evs if e.name.startswith("serving.")
                 and e is not tick
                 and tick.start <= e.start and e.end <= tick.end]
        names = [e.name for e in sorted(inner, key=lambda e: e.start)]
        assert names[0] == "serving.admit" and names[-1] == "serving.finish"
        assert ("serving.prefill" in names) == (n == 0)
        assert "serving.decode" in names
        for phase in ("serving.prefill", "serving.decode"):
            outer = [e for e in inner if e.name == phase]
            reads = [e for e in inner if e.name == phase + ".readback"]
            assert len(reads) == len(outer)
            for o, r in zip(outer, reads):
                assert o.start <= r.start and r.end <= o.end


class _Watched:
    """A step program's output that logs every conversion to a host
    value (other than through `jax.device_get`, which unwraps it)."""

    def __init__(self, x, log):
        self.x, self.log = x, log

    def _read(self, how):
        self.log.append(how)
        return np.asarray(self.x)

    def __array__(self, dtype=None, copy=None):
        return self._read("np.asarray")

    def __int__(self):
        return int(self._read("int"))

    def __float__(self):
        return float(self._read("float"))

    def __bool__(self):
        return bool(self._read("bool"))

    def __index__(self):
        return int(self._read("index"))

    def __getitem__(self, i):
        return self._read("getitem")[i]

    def item(self):
        return self._read("item").item()

    def tolist(self):
        return self._read("tolist").tolist()


@pytest.mark.parametrize("layout", LAYOUTS)
def test_one_device_to_host_read_per_step_call(monkeypatch, layout):
    eng = _engine(layout, n_slots=4, eos_id=-1)
    log = []
    names = ["_decode_fn", "_chunk_fn"] + (
        ["_decode_paged_fn", "_chunk_paged_fn"] if layout == "paged" else [])
    for name in names:
        fn = getattr(eng, name)

        def watched(*a, _fn=fn, _name=name):
            log.append("call " + _name)
            out = _fn(*a)
            # output 1 is the KV cache, which stays on the device
            return tuple(o if i == 1 else jax.tree.map(
                lambda x: _Watched(x, log), o) for i, o in enumerate(out))
        setattr(eng, name, watched)
    device_get = jax.device_get

    def counted(x):
        log.append("device_get")
        return device_get(jax.tree.map(
            lambda w: w.x if isinstance(w, _Watched) else w, x))
    monkeypatch.setattr(jax, "device_get", counted)
    for p in CONTRACT:
        eng.submit(p, max_new=3)
    eng.run()
    calls = [i for i, e in enumerate(log) if e.startswith("call ")]
    assert {log[i] for i in calls} >= {"call _decode_fn", "call _chunk_fn"} \
        if layout == "contiguous" else \
        {"call _decode_paged_fn", "call _chunk_paged_fn"}
    for i, j in zip(calls, calls[1:] + [len(log)]):
        assert log[i + 1:j] == ["device_get"], (log[i], log[i + 1:j])


# the values the decode program's device counters gave before they were
# counted on the host: (sampled_tokens, eos_sampled, nonfinite_logit_rows)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("workload,want", [
    ("contract", (40, 0, 0)),
    ("contract_eos", (28, 2, 0)),
    ("nan_logits", (16, 0, 4)),
])
def test_decode_counters_keep_their_values(layout, workload, want):
    if workload == "nan_logits":
        inj = FaultInjector(FaultPlan([
            Fault("serving.logits", 0, "nan_logits"),
            Fault("serving.logits", 1, "inf_logits")]))
        eng = _engine(layout, n_slots=2, eos_id=-1, faults=inj)
        for p in CONTRACT[:3]:
            eng.submit(p, max_new=4)
    else:
        eng = _engine(layout, CFG32, PARAMS32, n_slots=4,
                      eos_id=242 if workload == "contract_eos" else -1)
        for p in CONTRACT:
            eng.submit(p, max_new=5)
    eng.run()
    snap = eng.metrics_snapshot()
    got = tuple(snap.get(f"serving.decode.{k}", {"value": 0})["value"]
                for k in ("sampled_tokens", "eos_sampled",
                          "nonfinite_logit_rows"))
    assert got == want


@pytest.mark.parametrize("step,module", [
    ("decode", "jit__decode_step"), ("chunk", "jit__prefill_chunk_step")])
def test_step_programs_keep_their_module_names(step, module):
    eng = _engine("contiguous", n_slots=2, eos_id=-1)
    S, key, sel = 2, jax.random.PRNGKey(0), jnp.ones(2, bool)
    if step == "decode":
        low = eng._decode_fn.lower(PARAMS, eng.caches,
                                   jnp.zeros(S, jnp.int32), key, sel,
                                   jnp.int32(0))
    else:
        low = eng._chunk_fn.lower(PARAMS, eng.caches,
                                  jnp.zeros((S, 8), jnp.int32),
                                  jnp.zeros(S, jnp.int32), key, sel)
    assert low.as_text().split("\n", 1)[0].startswith(f"module @{module} ")
