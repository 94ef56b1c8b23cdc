"""Ahead-of-time compiles of the ingest kernels for one described TPU v5e.

Interpret mode (every other kernel test) never hands a kernel to the TPU
compiler, so it cannot see a layout Mosaic refuses or a block that does
not fit fast memory.  These tests compile the kernels the streaming engine
reaches — ``cms_update`` and the fused pass with static and dense routes,
explicit DMA double buffering on and off — at a deployment-size batch of
131,072 tuples, for a chip that is described, not attached; and the batch
path's all_to_all shuffle for the described 2x2 mesh.  Nothing runs.

The topology is described inside a fixture: only one process at a time may
load the TPU library, so no module may touch it while it is imported.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import plan_shares_skew, two_way
from repro.data import paper_2way

from repro.kernels.ingest_fused import (
    dense_route_encoding,
    fused_ingest_dense_pallas,
    fused_ingest_pallas,
)
from repro.kernels.sketch_update import cms_update_pallas
from repro.mapreduce.shuffle import distributed_program

N_ROWS = 131_072
SEEDS = (11, 222, 3333, 44444)  # StreamConfig.sketch_depth = 4
WIDTH = 2048  # StreamConfig.sketch_width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _routes(w: int):
    """A two-residual route table of width ``w`` with every term kind: an
    ordinary residual hashing B and excluding two heavy values, and a heavy
    residual pinned to one of them, each replicated ``w / 2`` times.
    Returns (routes, number of reducers)."""
    half = w // 2
    ordinary = (0, ((1, 0x9E3779B9, 61, 1),), tuple(range(0, 61 * half, 61)),
                (), ((1, (7, 31)),))
    heavy = (61 * half, ((0, 0x85EBCA6B, half, 1),), tuple(range(half)),
             ((1, 7),), ())
    return (ordinary, heavy), 62 * half


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_cms_update_compiles(one_chip):
    keys = jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip)
    _compile(
        lambda v: cms_update_pallas(v, SEEDS, WIDTH, interpret=False), keys
    )


@pytest.mark.parametrize("w_pad", [8, 32])
@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("routes_as", ["static", "dense"])
def test_fused_ingest_compiles(one_chip, routes_as, double_buffer, w_pad):
    rows = jax.ShapeDtypeStruct((N_ROWS, 2), jnp.int32, sharding=one_chip)
    routes, num_reducers = _routes(w_pad)
    common = dict(
        sketch_cols=(1,), seeds=SEEDS, width=WIDTH, interpret=False,
        double_buffer=double_buffer,
    )
    if routes_as == "static":
        _compile(
            lambda r: fused_ingest_pallas(
                r, routes=routes, num_reducers=num_reducers, **common
            ),
            rows,
        )
    else:
        enc = {
            k: jax.ShapeDtypeStruct(np.shape(v), jnp.int32, sharding=one_chip)
            for k, v in dense_route_encoding(routes, 2, w_pad, 8).items()
        }
        k_pad = -(-num_reducers // 128) * 128
        _compile(
            lambda r, e: fused_ingest_dense_pallas(r, e, k_pad=k_pad, **common),
            rows, enc,
        )


def test_distributed_shuffle_compiles_on_2x2_mesh(topo):
    """The four-chip path of ``run_distributed``: one program over the
    2x2 mesh, with the exchange lowered to an all-to-all."""
    query = two_way()
    data = paper_2way(np.random.default_rng(0), n_r=4000, n_s=400)
    plan = plan_shares_skew(query, data, q=100)
    mesh = Mesh(np.array(topo.devices), ("shuffle",))
    shard = NamedSharding(mesh, PartitionSpec("shuffle"))
    rows = tuple(
        jax.ShapeDtypeStruct((len(data[r.name]), r.arity), jnp.int32,
                             sharding=shard)
        for r in query.relations
    )
    masks = tuple(
        jax.ShapeDtypeStruct((x.shape[0],), jnp.bool_, sharding=shard)
        for x in rows
    )
    fn = distributed_program(query, plan, mesh)
    text = fn.lower(rows, masks).compile().as_text()
    assert "all-to-all" in text
