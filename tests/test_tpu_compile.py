"""The fused plan-scan kernels compile for a TPU v5e chip.

Interpret mode (every other pallas test) cannot see what the chip's
compiler refuses: scalar stores to vector memory, blocks that break the
(8, 128) tiling rule, register casts Mosaic cannot lower.  These tests
compile each kernel of the served path for a *described* v5e:2x2 chip
(nothing runs) at the paper's §VII-C scale, ``scaled_cluster(100_000,
100)`` (10M configurations), on the simulator's DB cost surfaces, with
``interpret=False``, and check that the compiled program holds the
kernel (a ``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import repro.core.cost_model  # noqa: F401  (registers the DB surfaces)
from repro.analysis.registry import iter_cost_surfaces
from repro.core.cluster import scaled_cluster
from repro.kernels.plan_scan import (LANES, SUBLANES, PallasPlanBackend,
                                     build_neighbor_step, build_scan,
                                     build_scan_sharded)

CLUSTER = scaled_cluster(100_000, 100)
BLOCK = 1 << 15                  # PallasPlanBackend's compiled block
P_WIDTH = 2                      # DB surfaces take params = [ss, ls]
SURFACES = ("db/sim/SMJ", "db/sim/BHJ")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _surface_fn(name):
    surface = {s.name: s for s in iter_cost_surfaces("db")}[name]
    return surface.make_fn(jnp)


def _assert_kernel(prog, *args):
    text = prog.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_compiled_geometry_is_whole_vreg_tiles():
    """The geometry compiled below is the one the backend dispatches."""
    be = PallasPlanBackend(interpret=False, devices=1)
    assert be.block == BLOCK and BLOCK % (SUBLANES * LANES) == 0
    assert be._block_rows(1000) == SUBLANES * LANES   # paper_cluster(100, 10)
    assert be._block_rows(CLUSTER.grid_size()) == BLOCK


@pytest.mark.parametrize("surface", SURFACES)
@pytest.mark.parametrize("nq", [0, 8])
def test_scan_compiles_for_v5e(one_chip, surface, nq):
    nb = -(-CLUSTER.grid_size() // BLOCK)
    prog = build_scan(_surface_fn(surface), CLUSTER, block=BLOCK, nb=nb,
                      nq=nq, lo0=0, has_params=True, p_width=P_WIDTH,
                      masked=True, interpret=False)
    params = jax.ShapeDtypeStruct((max(1, nq) * P_WIDTH,), jnp.float32,
                                  sharding=one_chip)
    _assert_kernel(prog, params)


@pytest.mark.parametrize("surface", SURFACES)
def test_neighbor_step_compiles_for_v5e(one_chip, surface):
    n_starts = 26                # 2 corners + 24 random (ensemble mode)
    prog = build_neighbor_step(_surface_fn(surface), CLUSTER,
                               n_starts=n_starts, has_params=True,
                               p_width=P_WIDTH, interpret=False)
    cur_t = jax.ShapeDtypeStruct((CLUSTER.n_dims, n_starts), jnp.int32,
                                 sharding=one_chip)
    params = jax.ShapeDtypeStruct((P_WIDTH,), jnp.float32, sharding=one_chip)
    _assert_kernel(prog, cur_t, params)


@pytest.mark.parametrize("surface", SURFACES)
@pytest.mark.parametrize("nq", [0, 8])
def test_sharded_scan_compiles_for_v5e_2x2(topo, surface, nq):
    n_dev = 4
    mesh = jax.sharding.Mesh(topo.devices[:n_dev], ("plan",))
    nb_shard = -(-CLUSTER.grid_size() // (BLOCK * n_dev))
    prog = build_scan_sharded(_surface_fn(surface), CLUSTER, block=BLOCK,
                              nb_shard=nb_shard, n_dev=n_dev, nq=nq,
                              has_params=True, p_width=P_WIDTH, mesh=mesh,
                              interpret=False)
    params = jax.ShapeDtypeStruct((max(1, nq) * P_WIDTH,), jnp.float32,
                                  sharding=NamedSharding(mesh,
                                                         PartitionSpec()))
    _assert_kernel(prog, params)
