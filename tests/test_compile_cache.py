"""The persistent compile cache lands where ``enable_compile_cache`` says.

Each check runs in a child interpreter on the CPU backend, so the cache
setting never leaks into the other tests of this process.
"""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, path
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()
print(path)
"""


def _child(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(compile=compile_)], env=env,
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_env_dir_receives_the_compiled_entries(tmp_path):
    cache = tmp_path / "cache"
    assert _child(cache, True) == str(cache)
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_default_dir_is_fixed_inside_the_checkout_and_ignored():
    path = _child(None, False)
    assert Path(path) == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
