"""The jax plan backend's programs compile for a TPU v5e chip.

A CPU run cannot see what the chip's compiler refuses: layouts that break
its tiling, memory a program may not use, a program it cannot partition.
These tests build each program the served path launches — the single
scan ``plan_scan``, the stacked scan ``plan_scan_many_w{Qpad}`` and the
stacked climb ``plan_climb_many`` on one chip, and both scans
``shard_map``'d over a 4-chip plan mesh — at the paper's §VII-C scale,
``scaled_cluster(100_000, 100)`` (10M configurations), on the simulator's
DB cost surfaces, and compile them for a *described* v5e:2x2 chip
(nothing runs).  Each must come out a TPU program, and a scan of the 10M
grid must loop over its spans on the device.

The backend builds each program with its own geometry and memo key; the
test takes it from the program memo before the launch, which could not
run here, and lowers it on the described chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

import repro.core.cost_model  # noqa: F401  (registers the DB surfaces)
from repro.analysis.registry import iter_cost_surfaces
from repro.core.cluster import paper_cluster, scaled_cluster
from repro.core.planning_backend import JaxPlanBackend, start_indices

CLUSTER = scaled_cluster(100_000, 100)
P_WIDTH = 2                      # DB surfaces take params = [ss, ls]
Q = 8                            # stacked requests: plan_scan_many_w8
N_STARTS = 24                    # random climb starts (ensemble mode)
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


def _surface_fn(name):
    surface = {s.name: s for s in iter_cost_surfaces("db")}[name]
    return surface.make_fn(jnp)


class _Built(Exception):
    """Stops a launch once its program is built."""


def _program(be, launch):
    """The program ``launch()`` would run on ``be``: built by the backend
    from its own geometry (``_program``, the memo every launch goes
    through, is handed the build), but not launched."""
    built = []

    def program(kind, fn, cluster, extra, build):
        built.append(build())
        raise _Built
    be._program = program
    with pytest.raises(_Built):
        launch()
    return built[0]


def _backend(topo, n_dev):
    """A jax backend planning on ``n_dev`` of the described chips, and
    the sharding its arguments take there."""
    be = JaxPlanBackend(devices=n_dev)
    if n_dev == 1:
        return be, SingleDeviceSharding(topo.devices[0])
    # the plan mesh the backend would build from the host's devices
    be._ndev = n_dev
    be._mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("plan",))
    return be, NamedSharding(be._mesh, PartitionSpec())


def _scan(topo, n_dev, surface, name, cluster=CLUSTER):
    """The compiled text of scan program ``name`` (``plan_scan`` or
    ``plan_scan_many_w8``) over ``cluster``, lowered as it is launched:
    ``prog(0, params)``."""
    be, on = _backend(topo, n_dev)
    fn = _surface_fn(surface)
    if name == "plan_scan":
        prog = _program(be, lambda: be.argmin_grid(
            fn, cluster, params=np.ones(P_WIDTH)))
        p_shape = (P_WIDTH,)
    else:
        prog = _program(be, lambda: be.argmin_grid_many_async(
            fn, cluster, np.ones((Q, P_WIDTH))))
        p_shape = (Q, P_WIDTH)
    lo = jax.ShapeDtypeStruct((), jnp.int32, weak_type=True, sharding=on)
    params = jax.ShapeDtypeStruct(p_shape, jnp.float32, sharding=on)
    return prog.lower(lo, params).compile().as_text()


def _assert_tpu_program(text, name):
    head = text.splitlines()[0]
    assert head.startswith(f"HloModule jit_{name},"), head[:80]
    assert ":T(" in head          # the TPU compiler's tiled layouts


def _span_loop(text, name):
    """Whether the compiled program loops over its spans on the device
    (``_over_spans``' ``fori_loop``)."""
    return f'op_name="jit({name})/while"' in text


@pytest.mark.parametrize("surface", SURFACES)
@pytest.mark.parametrize("name", ["plan_scan", f"plan_scan_many_w{Q}"])
def test_scan_compiles_for_v5e(topo, surface, name):
    text = _scan(topo, 1, surface, name)
    _assert_tpu_program(text, name)
    assert _span_loop(text, name)


@pytest.mark.parametrize("surface", SURFACES)
def test_stacked_climb_compiles_for_v5e(topo, surface):
    be, on = _backend(topo, 1)
    prog = _program(be, lambda: be.hill_climb_ensemble_many_async(
        _surface_fn(surface), CLUSTER, np.ones((Q, P_WIDTH)),
        n_random=N_STARTS, seed=0))
    starts = jax.ShapeDtypeStruct(
        start_indices(CLUSTER, None, N_STARTS, 0).shape, jnp.int32,
        sharding=on)
    params = jax.ShapeDtypeStruct((Q, P_WIDTH), jnp.float32, sharding=on)
    _assert_tpu_program(prog.lower(starts, params).compile().as_text(),
                        "plan_climb_many")


@pytest.mark.parametrize("surface", SURFACES)
@pytest.mark.parametrize("name", ["plan_scan", f"plan_scan_many_w{Q}"])
def test_sharded_scan_compiles_for_v5e_2x2(topo, surface, name):
    text = _scan(topo, 4, surface, name)
    _assert_tpu_program(text, name)
    assert "num_partitions=4" in text.splitlines()[0]
    assert _span_loop(text, name)
    assert "all-reduce(" in text  # the per-shard bests meet on every chip


def test_grid_size_sets_the_span_loop(topo):
    """The 10M grid compiles to the looped program; the 1,000
    configurations of ``paper_cluster(100, 10)`` fit one span and
    compile to the span body alone."""
    name = f"plan_scan_many_w{Q}"
    small = paper_cluster(100, 10)
    assert small.grid_size() == 1000
    assert _span_loop(_scan(topo, 1, SURFACES[0], name), name)
    text = _scan(topo, 1, SURFACES[0], name, cluster=small)
    _assert_tpu_program(text, name)
    assert " while(" not in text
