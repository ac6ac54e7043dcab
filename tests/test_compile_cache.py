"""Where the persistent compilation cache lands: $JAX_COMPILATION_CACHE_DIR
when set, else the fixed <repo>/.jax_cache.  Each case runs in a fresh
process, since JAX picks its cache directory once per process."""
import os
import subprocess
import sys

ROOT = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))

PROBE = """
import jax
from repro.launch.compile_cache import enable_compile_cache
used = enable_compile_cache()
if COMPILE:
    jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()
print(used)
print(jax.config.jax_compilation_cache_dir)
"""


def run_probe(compile_: bool, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
                **env)
    r = subprocess.run(
        [sys.executable, "-c", PROBE.replace("COMPILE", str(compile_))],
        capture_output=True, text=True, timeout=120, env=full, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()[-2:]


def test_env_dir_is_used_as_set(tmp_path):
    cache = str(tmp_path / "cache")
    used, configured = run_probe(
        True, JAX_COMPILATION_CACHE_DIR=cache,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert used == configured == cache
    assert any(n.endswith("-cache") for n in os.listdir(cache))


def test_default_dir_is_fixed_in_repo():
    used, configured = run_probe(False)
    assert used == configured == os.path.join(ROOT, ".jax_cache")
