"""Compile the serving path and the Pallas kernels for a described TPU v5e.

Nothing runs: XLA's TPU compiler, which is installed even where no chip
is attached, compiles for a ``v5e:2x2`` topology that is described, not
present.  What it refuses here (an unaligned block, a primitive Mosaic
cannot lower, a sharding it cannot partition) would be refused on the
chip.  The topology is described inside a fixture, so that only the
worker running this file loads the TPU library, and every worker
collects the same tests.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.batched.engine import fused_batch_program
from repro.distributed.sharding import data_shards, slot_batch_spec
from repro.kernels import CERT_SHAPES
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.mamba2_ssd import mamba2_ssd_fwd
from repro.kernels.rwkv6_scan import rwkv6_wkv_fwd
from repro.launch.mesh import make_local_mesh
from repro.perception.data import H, W
from repro.scenarios.replay import replay_ladder

RUNGS = list(replay_ladder().rungs)
KERNELS = {
    "flash_attention": flash_attention_fwd,
    "decode_attention": decode_attention_fwd,
    "rwkv6_wkv": rwkv6_wkv_fwd,
    "mamba2_ssd": mamba2_ssd_fwd,
}
DTYPES = {"f32": jnp.float32, "i32": jnp.int32}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rung", RUNGS, ids=lambda r: r.name)
def test_rung_step_compiles_on_one_chip(one_chip, rung):
    """Each rung's fused batched step at capacity 8 on one v5e chip."""
    raw = jax.ShapeDtypeStruct((8, H, W, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(fused_batch_program(rung.build())).lower(raw).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("rung", RUNGS, ids=lambda r: r.name)
def test_rung_step_compiles_on_data4_mesh(topo, rung):
    """The same step at capacity 32 over a data=4 mesh, partitioned the
    way the executor pins it: slot dim over ``data`` in and out."""
    mesh = make_local_mesh(data=4, devices=topo.devices)
    assert data_shards(mesh) == 4
    slots = NamedSharding(mesh, slot_batch_spec(mesh, 32))
    assert slots.spec == P("data")
    raw = jax.ShapeDtypeStruct((32, H, W, 3), jnp.float32, sharding=slots)
    step = jax.jit(fused_batch_program(rung.build()), out_shardings=slots)
    compiled = step.lower(raw).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_at_cert_shapes(one_chip, name):
    """Every Pallas kernel lowers through Mosaic into a TPU custom call."""
    args = [jax.ShapeDtypeStruct(shape, DTYPES[dt], sharding=one_chip)
            for dt, shape in CERT_SHAPES[name]]
    fn = functools.partial(KERNELS[name], interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
