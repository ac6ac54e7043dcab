"""Compiles for a described TPU v5e (no chip attached): the Pallas
kernels at widths the configs use, the serving engine's decode and
chunk-prefill programs at phi3-mini width (2 layers), and the SIMT
machine's run loop.  The chip's compiler refuses here what it would
refuse on the chip: unaligned slices, primitives Mosaic cannot lower,
programs that do not fit the device's memory."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import api

V5E_HBM_BYTES = 16 * 2**30        # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled


# ------------------------------------------------------------------ kernels

def test_flash_attention_head_dim_96(spec):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    q = spec((1, 32, 1024, 96), jnp.bfloat16)           # phi3-mini heads
    c = compile_for_chip(lambda q, k, v: flash_attention_fwd(q, k, v), q, q,
                         q)
    assert "tpu_custom_call" in c.as_text()


def test_rmsnorm_d_3072(spec):
    from repro.kernels.rmsnorm.kernel import rmsnorm_fwd
    c = compile_for_chip(rmsnorm_fwd, spec((256, 3072), jnp.bfloat16),
                         spec((3072,), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gather_d_2048(spec, dtype):
    from repro.kernels.moe_dispatch.kernel import moe_gather_fwd
    E, C = 64, 16                                       # olmoe: 64 experts
    c = compile_for_chip(lambda x, st: moe_gather_fwd(x, st, E, C),
                         spec((512, 2048), dtype), spec((E * C,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_intra_zamba2(spec):
    from repro.kernels.ssm_scan.kernel import ssd_intra_fwd
    B, nc, Q, H, P, N = 1, 4, 256, 112, 64, 64          # zamba2-7b SSD
    f32 = jnp.float32
    c = compile_for_chip(ssd_intra_fwd, spec((B, nc, Q, H), f32),
                         spec((B, nc, Q, H, P), f32), spec((B, nc, Q, N), f32),
                         spec((B, nc, Q, N), f32))
    assert "tpu_custom_call" in c.as_text()


# ------------------------------------------------------------ serving steps

@pytest.fixture(scope="module")
def phi3_2l(spec):
    """phi3-mini at full width, 2 layers: param shapes on the chip."""
    cfg = get_config("phi3-mini-3.8b").replace(num_layers=2)
    shapes = jax.eval_shape(
        lambda: api.build_params(jax.random.PRNGKey(0), cfg))
    return cfg, jax.tree.map(lambda s: spec(s.shape, s.dtype), shapes)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_engine_step_compiles(spec, phi3_2l, layout, step):
    from repro.serving.engine import Engine
    cfg, params = phi3_2l
    S, L, C = 4, 1024, 32
    eng = Engine(cfg, None, n_slots=S, max_len=L, prefill_chunk=C,
                 eos_id=-1, kv_layout=layout)
    caches = jax.tree.map(lambda x: spec(x.shape, x.dtype), eng.caches)

    def i32(*shape):
        return spec(shape, jnp.int32)

    def flag(*shape):
        return spec(shape, jnp.bool_)
    key = spec((2,), jnp.uint32)
    if layout == "contiguous":
        fn = eng._decode_fn if step == "decode" else eng._chunk_fn
        args = ((params, caches, i32(S), key, flag(S), i32())
                if step == "decode" else
                (params, caches, i32(S, C), i32(S), key, flag(S)))
    else:
        pps = eng._kv.pages_per_slot
        tabs = (i32(S), i32(S, pps), i32(S, pps), flag(S, pps))
        fn = eng._decode_paged_fn if step == "decode" else eng._chunk_paged_fn
        args = ((params, caches) + tabs + (i32(S), key, flag(S), i32())
                if step == "decode" else
                (params, caches) + tabs + (i32(S, C), i32(S), key))
    m = fn.lower(*args).compile().memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.alias_size_in_bytes > 0          # the KV buffer is donated
    assert used < V5E_HBM_BYTES, used


# --------------------------------------------------------------------- simt

def test_simt_run_loop_compiles(spec):
    import chip_smoke
    from benchmarks.fig9_rodinia import BENCHES, machine_config
    from repro.core.simt import machine
    w, t = chip_smoke.SIMT_CONFIG
    for name in chip_smoke.SIMT_BENCHES:
        mc = machine_config(w, t, BENCHES[name][1])
        st = jax.eval_shape(lambda mc=mc: machine.init_state(mc))
        st = jax.tree.map(lambda s: spec(s.shape, s.dtype), st)
        machine._run_jit.lower(mc, spec((512,), jnp.uint32), st).compile()
