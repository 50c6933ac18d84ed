"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Interpret-mode tests cannot show what the TPU's compiler refuses: a
float64 operand or an int64 index inside a Mosaic kernel, an
unsupported relayout, a program that cannot be partitioned over a mesh.
These tests compile the planner's device programs for a ``v5e:2x2``
topology that is described, not attached, at the widths the planner
runs: the ``masked_select`` kernel at cluster B's ×1 and ×8 device
counts, the single-cluster chunk program with the TPU's default select
backend, and the sharded chunk program on the 4-device mesh.

The topology is described inside a module fixture, so only the worker
that runs this file loads the TPU compiler, and the persistent
compilation cache is off around the compiles (an entry compiled for a
described chip cannot be read back without one).
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          SingleDeviceSharding)

from repro.core.clustergen import cluster_a, cluster_f  # noqa: E402
from repro.core.equilibrium_batch import BatchPlanner, _plan_chunk  # noqa: E402
from repro.core.shard import (_CONST_SPECS, _DYN_SPECS,  # noqa: E402
                              ShardedBatchPlanner, shard_chunk_program)
from repro.kernels.ops import masked_select  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(x, sharding):
    a = jnp.asarray(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _carry(make):
    """A built planner's chunk-program arguments (real shapes, CPU)."""
    with jax.enable_x64(True):
        bp = BatchPlanner(make())
        bp.sync()
    return bp


@pytest.mark.parametrize("n_dev", [995, 7960])   # cluster B ×1 / ×8
def test_masked_select_compiles_for_v5e(one_chip, n_dev):
    with jax.enable_x64(True):
        compiled = masked_select.lower(
            jax.ShapeDtypeStruct((8, n_dev), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((n_dev,), jnp.int32, sharding=one_chip),
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "f64" not in text


@pytest.mark.parametrize("make", [cluster_a, cluster_f],
                         ids=["A", "F"])
def test_plan_chunk_compiles_for_v5e(one_chip, make):
    """The chunk program as a TPU runs it: x64 criteria math (emulated
    by XLA) around the Mosaic-compiled select kernel."""
    bp = _carry(make)
    args = jax.tree.map(lambda x: _spec(x, one_chip),
                        (bp._dyn, bp._const, bp._slack, bp._headroom,
                         bp._min_dvar))
    with jax.enable_x64(True):
        compiled = _plan_chunk.lower(
            *args, k=bp._k, kb=bp._kb, rb=bp._rb, m=bp.chunk,
            backend="pallas-tpu", cached=False, bounds=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0          # the carry is donated


def test_sharded_chunk_compiles_for_v5e_mesh(topo):
    n = len(topo.devices)
    with jax.enable_x64(True):
        # the planner's own mesh padding, for the described mesh's width
        # (it only plans on the visible devices, so nothing dispatches)
        sp = ShardedBatchPlanner(cluster_f(), n_shards=1)
        sp.n_shards = n
        sp.sync()
    mesh = Mesh(np.asarray(topo.devices), ("dev",))

    def specs(tree, pspecs):
        return tuple(_spec(x, NamedSharding(mesh, p))
                     for x, p in zip(tree, pspecs))

    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    with jax.enable_x64(True):
        # slack, headroom, min_dvar and the real device count n_real
        scalars = [_spec(x, rep) for x in (sp._slack, sp._headroom,
                                           sp._min_dvar,
                                           np.float64(sp._n_real))]
        compiled = shard_chunk_program(mesh).lower(
            specs(sp._dyn, _DYN_SPECS), specs(sp._const, _CONST_SPECS),
            *scalars, k=sp._k, kb=sp._kb, rb=sp._rb, m=sp.chunk,
            backend=sp.select_backend, bounds=True).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" in text
    assert compiled.memory_analysis().argument_size_in_bytes > 0
