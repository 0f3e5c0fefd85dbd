"""The main path's device programs compile for a TPU v5e at full width.

No chip is attached: the TPU compiler compiles for a described v5e:2x2, so
what Mosaic or XLA would refuse on the chip (tiling, VMEM, HBM fit, a
kernel that cannot be partitioned) fails here at no chip time. Nothing
runs, so these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file. Keep these tests in this one file, so one worker loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

P_KERNEL, N_FULL, P_BATCH = 10_240, 50_176, 1_024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep it out."""
    from jax._src import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def batch():
    """One engine batch of the bench workload at full node width, encoded
    on the host exactly as the engine encodes it."""
    from bench_workload import bench_plugin_set, make_workload
    from minisched_tpu.encode import NodeFeatureCache, encode_pods

    make_nodes, make_pods = make_workload(50_000, P_BATCH, seed=0)
    cache = NodeFeatureCache(capacity=50_000)
    for node in make_nodes():
        cache.upsert_node(node)
    eb = encode_pods(make_pods(), P_BATCH, registry=cache.registry)
    nf, _ = cache.snapshot(pad=N_FULL)
    af = cache.snapshot_assigned()
    return bench_plugin_set(), (eb, nf, af, jax.random.PRNGKey(0))


def _shapes(tree, sharding=None):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_pallas_kernel_compiles_at_full_width(one_chip):
    from minisched_tpu.ops.pallas_select import greedy_assign_pallas
    from minisched_tpu.state.objects import RESOURCES

    r = len(RESOURCES)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = (jax.ShapeDtypeStruct((P_KERNEL, N_FULL), jnp.float32),
            jax.ShapeDtypeStruct((P_KERNEL, r), jnp.float32),
            jax.ShapeDtypeStruct((N_FULL, r), jnp.float32), key)
    compiled = greedy_assign_pallas.lower(
        *_shapes(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kw,kernel", [
    ({"pallas": True}, True),       # the Pallas kernel step
    ({"shortlist": 128}, False),    # the engine's default shortlist step
])
def test_step_compiles_at_one_engine_batch(one_chip, batch, kw, kernel):
    from minisched_tpu.ops import build_step

    plugin_set, args = batch
    step = build_step(plugin_set, explain=False, **kw)
    compiled = step.lower(*_shapes(args, one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel


def test_greedy_sharded_step_compiles_on_2x2_mesh(topo, no_persistent_cache,
                                                  batch):
    from minisched_tpu.parallel.mesh import make_mesh
    from minisched_tpu.parallel.sharded import build_sharded_step

    plugin_set, args = batch
    mesh = make_mesh(topo.devices)
    assert mesh.devices.shape == (2, 2)
    step = build_sharded_step(plugin_set, mesh, *args[:3],
                              assignment="greedy")
    compiled = step.lower(*_shapes(args)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
