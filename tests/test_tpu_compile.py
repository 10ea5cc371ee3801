"""Compiles for a described TPU v5e (no chip attached): the fused
executor's Pallas counter bump at the Faces counter shape, alone on one
chip and inside ``shard_map`` over a 2x2 mesh, and the whole fused Faces
program on that mesh with the Pallas backend selected by the mesh's
platform. What the chip's compiler refuses (tiling, VMEM, partitioning)
fails here at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only the process that runs these tests loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import STStream, halo
from repro.core.compat import shard_map
from repro.core.engine import _pallas_bump, fused_executable

NPEERS = len(halo.DIRECTIONS)         # Faces counter slots per rank: 26
AXES = ("x", "y", "z")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2, 1), AXES)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def test_pallas_bump_compiles_one_chip(topo):
    one = SingleDeviceSharding(topo.devices[0])
    sig = jax.ShapeDtypeStruct((1, NPEERS), jnp.int32, sharding=one)
    compiled = jax.jit(_pallas_bump).lower(sig, sig).compile()
    assert _has_kernel(compiled)


def test_pallas_bump_compiles_in_shard_map_2x2(mesh_2x2):
    spec = P(AXES)
    sig = jax.ShapeDtypeStruct((4, NPEERS), jnp.int32,
                               sharding=NamedSharding(mesh_2x2, spec))
    bump = shard_map(_pallas_bump, mesh=mesh_2x2, in_specs=(spec, spec),
                     out_specs=spec)
    compiled = jax.jit(bump).lower(sig, sig).compile()
    assert _has_kernel(compiled)


def test_fused_faces_program_compiles_2x2(mesh_2x2):
    stream = STStream(mesh_2x2, AXES)
    n = (8, 8, 8)
    halo.build_faces_program(stream, n, niter=2)
    prog, = stream.scheduled_programs(throttle="adaptive", resources=16,
                                      fused=True)
    shapes = {}
    for win in stream.windows.values():
        shapes.update(jax.eval_shape(lambda w=win: w.allocate(4)))
    keys = tuple(sorted(shapes))
    sharding = NamedSharding(mesh_2x2, stream.state_spec())
    args = [jax.ShapeDtypeStruct(shapes[k].shape, shapes[k].dtype,
                                 sharding=sharding) for k in keys]
    jfn = fused_executable(stream, prog, keys, donate=False)
    assert prog.meta["fusion_backend"] == "pallas"
    compiled = jfn.lower(*args).compile()
    assert _has_kernel(compiled)
    assert "collective-permute" in compiled.as_text()
