"""Parameter-spec machinery.

Models are described as trees of ``ParamSpec`` (shape + logical axes + init).
From one spec tree we derive:
  * materialized params            (init_params)          — smoke tests, train
  * jax.ShapeDtypeStruct stand-ins (abstract_params)      — dry-run, NO alloc
  * PartitionSpecs                 (param_pspecs)         — pjit shardings

This guarantees shapes/axes/shardings can never diverge between the smoke
path and the 512-device dry-run path.
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                   # logical axis name (or None) per dim
    init: str = "normal"          # normal | zeros | ones
    scale: Optional[float] = None  # stddev for normal (None -> 1/sqrt(fan_in))
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _tree_map_specs(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


def _fan_in(shape) -> int:
    if len(shape) == 0:
        return 1
    if len(shape) == 1:
        return shape[0]
    return int(np.prod(shape[:-1]))


def _init_leaf(spec: ParamSpec, key, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    std = spec.scale if spec.scale is not None else 1.0 / np.sqrt(_fan_in(spec.shape))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def init_params(spec_tree, rng, dtype=jnp.float32):
    """Materialize params. Each leaf gets a key derived from its tree path
    (a CRC32 digest, stable across processes, unlike the per-process
    salted ``hash``), so adding/removing params never reshuffles other
    inits and one seed gives the same weights in every process."""
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=is_spec)[0]
    treedef = jax.tree.structure(spec_tree, is_leaf=is_spec)
    out = []
    for path, spec in leaves_with_paths:
        pstr = jax.tree_util.keystr(path)
        key = jax.random.fold_in(rng, zlib.crc32(pstr.encode()) % (2**31))
        out.append(_init_leaf(spec, key, spec.dtype if dtype is None else dtype))
    return jax.tree.unflatten(treedef, out)


def abstract_params(spec_tree, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins — safe at any scale, no allocation."""
    return _tree_map_specs(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype if dtype is not None else s.dtype),
        spec_tree)


def param_axes(spec_tree):
    return _tree_map_specs(lambda s: s.axes, spec_tree)


def param_pspecs(spec_tree, rules):
    """Tree of PartitionSpec derived via sharding rules."""
    return _tree_map_specs(lambda s: rules.pspec(s.axes), spec_tree)


def param_count(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=is_spec)
    return int(sum(np.prod(s.shape) for s in leaves))


def stack_specs(spec_tree, n: int):
    """Stack a spec tree along a new leading 'layers' axis (for scan groups)."""
    return _tree_map_specs(
        lambda s: dataclasses.replace(
            s, shape=(n,) + tuple(s.shape), axes=("layers",) + tuple(s.axes)),
        spec_tree)
