"""GPipe (shard_map + ppermute) equivalence vs sequential execution,
forward AND backward, in an 8-device subprocess."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.pipeline import gpipe_apply

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
key = jax.random.PRNGKey(0)
L, B, S, d = 8, 8, 16, 32
ws = jax.random.normal(key, (L, d, d)) * 0.2
bs = jax.random.normal(key, (L, d)) * 0.1
x = jax.random.normal(key, (B, S, d))

def body(stage_p, h):       # applies this stage's layers sequentially
    w, b = stage_p
    def one(h, p):
        wi, bi = p
        return jnp.tanh(h @ wi + bi), None
    h, _ = jax.lax.scan(one, h, (w, b))
    return h

def seq(params, x):
    w, b = params
    def one(h, p):
        wi, bi = p
        return jnp.tanh(h @ wi + bi), None
    h, _ = jax.lax.scan(one, x, (w, b))
    return h

ref = seq((ws, bs), x)
with mesh:
    out = jax.jit(lambda p, x: gpipe_apply(
        p, x, body, mesh=mesh, stage_axis="pod", n_micro=4))((ws, bs), x)
err = float(jnp.abs(out - ref).max())
print("fwd err:", err)
assert err < 1e-5

# backward equivalence
def loss_pipe(p, x):
    with mesh:
        return gpipe_apply(p, x, body, mesh=mesh, stage_axis="pod",
                           n_micro=4).sum()
def loss_seq(p, x):
    return seq(p, x).sum()
g1 = jax.jit(jax.grad(loss_pipe))((ws, bs), x)
g2 = jax.grad(loss_seq)((ws, bs), x)
gerr = max(float(jnp.abs(a - b).max()) for a, b in
           zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)))
print("grad err:", gerr)
assert gerr < 1e-4
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_gpipe_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=1200, env={**os.environ},
                       cwd=ROOT)
    assert "PIPELINE_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-3000:]
