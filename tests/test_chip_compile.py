"""Ahead-of-time compiles for a described TPU v5e (2x2), no chip needed.

The TPU compiler is installed with jax, and it compiles for a topology
that is only described.  These tests catch what interpret mode and the
CPU backend cannot: a Pallas block shape Mosaic refuses, an unaligned
slice, more scoped VMEM or SMEM than a kernel may use, an engine program
that does not fit or partition.  They compile; nothing runs.

The topology is described inside a module-scoped fixture (never while a
module is imported), which skips where it cannot be described, and keeps
all these compiles in this one file and this one process.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

# scale-20 Graph500 sizes (rmat:20: 1,048,576 vertices, 15,701,711 edges)
# for the q=1 block the fused kernel reads; tasks are kept few so each
# compile stays about a second
NB20, NNZ20 = 1 << 20, 15_701_711
FUSED_TASKS = 4096

# the benchmark's q=1 blocks (bench/configs): nb, nnz (= tasks), longest row
SEARCH_SHAPES = {
    "kron-s16": (1 << 16, 909_538, 247),
    "urand-s16": (1 << 16, 1_048_320, 30),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip: keep the cache off around these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tile,d", [(256, 16), (64, 128)])
def test_fused_kernel_compiles(one_chip, tile, d):
    from repro.kernels.tc_fused.tc_fused import fused_short_counts

    ptr = _sds((NB20 + 1,), jnp.int32, one_chip)
    idx = _sds((NNZ20,), jnp.int32, one_chip)
    tasks = _sds((FUSED_TASKS,), jnp.int32, one_chip)
    compiled = fused_short_counts.lower(
        ptr, idx, ptr, idx, tasks, tasks, _sds((), jnp.int32, one_chip),
        tile=tile, d=d, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["popcount", "mxu"])
def test_tile_kernel_compiles(one_chip, mode):
    from repro.kernels.tc_tile.tc_tile import TILE, WORDS, tile_triple_counts

    tiles = _sds((512, TILE, WORDS), jnp.uint32, one_chip)
    compiled = tile_triple_counts.lower(
        _sds((2048, 4), jnp.int32, one_chip), tiles, tiles, tiles,
        mode=mode, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q", [1, 2])
def test_cannon_engine_compiles(topo, monkeypatch, q):
    """The default-method Cannon program for an rmat:16 plan, under x64
    as ``tc_run`` sets it, in the formulation a TPU traces: on one
    described chip (q=1) and on a Mesh over the four described devices
    (q=2)."""
    from repro.core import count, rmat
    from repro.core.cannon import build_cannon_fn
    from repro.pipeline import PlanCache, plan_cannon

    # the engine asks the default backend (here the CPU) which
    # intersection to trace; steer it to the TPU's
    monkeypatch.setattr(count, "_equality_intersect", lambda: True)

    with jax.enable_x64(True):
        plan = plan_cannon(rmat(16, 16), q, cache=PlanCache(maxsize=0)).plan
        devices = np.array(topo.devices[: q * q]).reshape(q, q)
        fn = build_cannon_fn(
            plan, Mesh(devices, ("data", "model")), count_dtype=jnp.int64
        )
        structs = {
            k: _sds(v.shape, v.dtype, fn.shardings[k])
            for k, v in plan.device_arrays().items()
            if k in fn.shardings
        }
        compiled = fn.lower(**structs).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
    if q > 1:
        assert "collective-permute" in compiled.as_text()


@pytest.mark.parametrize("shape", sorted(SEARCH_SHAPES))
def test_search_fetch_has_no_per_task_loop(one_chip, monkeypatch, shape):
    """``search``'s TPU formulation at the benchmark's block shapes
    compiles to one ``while`` loop, the chunk scan: the window fetch is a
    native gather, not a loop of one ``dynamic-slice`` per task (a
    ``vmap`` of ``lax.dynamic_slice`` compiles to two such loops)."""
    from repro.core import count

    monkeypatch.setattr(count, "_equality_intersect", lambda: True)
    nb, nnz, dpad = SEARCH_SHAPES[shape]
    ptr = _sds((nb + 1,), jnp.int32, one_chip)
    idx = _sds((nnz,), jnp.int32, one_chip)
    fn = jax.jit(
        lambda ap, ai, bp, bi, ti, tj, c: count.count_pair_search(
            ap, ai, bp, bi, ti, tj, c, dpad=dpad, chunk=512
        )
    )
    compiled = fn.lower(
        ptr, idx, ptr, idx, idx, idx, _sds((), jnp.int32, one_chip)
    ).compile()
    assert len(re.findall(r"\swhile\(", compiled.as_text())) == 1
