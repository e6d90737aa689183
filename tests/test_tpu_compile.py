"""The served path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers a kernel for a *described* v5e chip (the
TPU compiler ships with jaxlib) and compiles it, which is where the chip's
compiler refuses what the CPU interpreter accepts — unaligned blocks,
primitives with no Mosaic lowering, more scoped VMEM than a kernel may
use. Shapes are the wildlife catalog's: d=1152 f32 rows, N=262144.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and with several test
workers every worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

N, D = 262144, 1152


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lower(case, spec):
    from repro.kernels.cosine_topk import ops as ct
    from repro.kernels.kmeans.kernel import assign_blocks

    store = spec((N, D))
    if case == "kmeans32":
        return assign_blocks.lower(store, spec((32, D)), interpret=False)
    if case == "kmeans1024":
        return assign_blocks.lower(store, spec((1024, D)), interpret=False)
    if case == "scalar":
        return ct.cosine_probe.lower(store, spec((D,)), spec((1,)), k=1,
                                     interpret=False)
    if case == "calibration_k500":
        # k above the in-kernel selection cap: whole tiles, merged in XLA
        return ct.cosine_probe_batch.lower(store, spec((4, D)),
                                           spec((4, 1)), k=500,
                                           interpret=False)
    b = 256 if case == "batch256_tiled" else 16
    preds, thr = spec((b, D)), spec((b, 1))
    if case == "masked16":
        return ct.cosine_probe_batch_masked.lower(
            store, spec((), jnp.int32), preds, thr, k=1, interpret=False)
    if case == "rowmask16":
        return ct.cosine_probe_batch_rowmask.lower(
            store, spec((N,), jnp.int32), preds, thr, k=1, interpret=False)
    return ct.cosine_probe_batch.lower(
        store, preds, thr, k=128 if case == "batch16_k128" else 1,
        interpret=False)


@pytest.mark.parametrize("case", [
    "batch16", "batch256_tiled", "batch16_k128", "calibration_k500",
    "masked16", "rowmask16", "scalar", "kmeans32", "kmeans1024",
])
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _lower(case, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_probe_compiles_for_v5e_2x2(topo, no_persistent_cache):
    """The --shards full-scan probe over a 4-chip ('data',) mesh: each
    shard runs the Mosaic kernel, then the O(B*k) psum / all-gather."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.histogram import make_sharded_probe

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    probe = jax.jit(make_sharded_probe(mesh, k=1, batched=True,
                                       impl="pallas", interpret=False))
    compiled = probe.lower(
        jax.ShapeDtypeStruct((4 * N, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((16, D), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((16, 1), jnp.float32, sharding=rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text
