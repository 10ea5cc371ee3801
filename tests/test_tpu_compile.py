"""Compiles for a described TPU v5e (no chip attached): the fused
executor's Pallas counter bump at the Faces counter shape, alone on one
chip and inside ``shard_map`` over a 2x2 mesh, the whole fused Faces
program on that mesh with the Pallas backend selected by the mesh's
platform, and a layer scan of the "ragged" MoE layer at Moonlight's
widths in each of its forms. What the chip's compiler refuses (tiling,
VMEM, partitioning) fails here at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only the process that runs these tests loads the
TPU library.
"""
import dataclasses
import os
import re

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


def _unfused_results(text):
    """The result type of every instruction of a compiled module that
    lies outside a fusion's body: what the program materialises."""
    bodies = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", text))
    out, outside = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            outside = head.group(1) not in bodies
        m = re.match(r"\s+(?:ROOT )?%\S+ = (\S+?)(?:\{\S*\})? [\w\-]+\(",
                     line)
        if outside and m:
            out.append(m.group(1))
    return out


@pytest.mark.parametrize("tokens,form", [(16, "dense"), (128, "grouped")])
def test_ragged_moe_scan_compiles_by_form(topo, tokens, form):
    """A 2-layer scan of the "ragged" MoE layer at Moonlight's widths (8
    held experts of 2048 x 1408, top-6 of 64): a 16-slot decode step's
    16 tokens take the dense form, whose einsums read each layer's
    expert slabs from the stack inside the dot fusions, with no
    ``ragged-dot`` and no materialised slab; past the dense form's limit
    the grouped ``ragged-dot`` runs, on a sliced-out copy of each slab."""
    from repro.configs import get_config
    from repro.models import moe as moe_mod
    from repro.models.params import is_spec
    from repro.sharding.rules import make_rules
    cfg = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           held=(0, 8)))
    assert (cfg.d_model, cfg.moe.expert_ff, cfg.moe.top_k) == (2048, 1408, 6)
    rules = make_rules(cfg, None, None)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((2,) + tuple(s.shape), jnp.bfloat16,
                                       sharding=one),
        moe_mod.moe_specs(cfg), is_leaf=is_spec)
    x = jax.ShapeDtypeStruct((tokens, 1, cfg.d_model), jnp.bfloat16,
                             sharding=one)

    def layers(p, x):
        def body(x, lp):
            return moe_mod.moe(cfg, lp, x, rules, impl="ragged")[0], None
        return jax.lax.scan(body, x, p)[0]

    text = jax.jit(layers).lower(params, x).compile().as_text()
    slabs = [t for t in _unfused_results(text)
             if re.fullmatch(r"bf16\[8,(2048,1408|1408,2048)\]", t)]
    assert moe_mod.routed_form(tokens) == form
    if form == "dense":
        assert "ragged-dot" not in text and not slabs
    else:
        assert "ragged-dot" in text and len(slabs) == 3
