"""Minimal parameter-definition system.

Models are defined as nested dicts of :class:`ParamDef`; the same tree yields
(1) materialized parameters, (2) PartitionSpecs via the logical-axis rules,
(3) ShapeDtypeStructs for allocation-free dry-runs, and (4) param counts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.distributed.sharding import spec_for

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]           # logical axis per dim
    init: str = "normal"                      # normal | zeros | ones | small
    scale: Optional[float] = None             # stddev override
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _tree_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_def)


def init_params(defs, key: Array, dtype_override=None):
    """Materialize a ParamDef tree into arrays (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = []
    for k, d in zip(keys, leaves):
        dt = dtype_override or d.dtype
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dt))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dt))
        else:
            fan_in = d.shape[0] if len(d.shape) >= 2 else max(d.shape[-1], 1)
            std = d.scale if d.scale is not None else 1.0 / math.sqrt(fan_in)
            if d.init == "small":
                std = (d.scale or 1.0) * 0.02
            # wait for each leaf: eager dispatch runs ahead of the device,
            # and every leaf's float32 intermediates would be live at once
            out.append(jax.block_until_ready(
                (jax.random.normal(k, d.shape, jnp.float32) * std).astype(dt)))
    return jax.tree.unflatten(treedef, out)


def abstract_params(defs, dtype_override=None):
    """ShapeDtypeStruct tree (for .lower() without allocation)."""
    return _tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype_override or d.dtype), defs)


def param_specs(defs, mesh: Optional[Mesh] = None):
    """PartitionSpec tree resolved against a mesh."""
    return _tree_map(lambda d: spec_for(d.shape, d.axes, mesh), defs)


def param_shardings(defs, mesh: Mesh):
    return _tree_map(lambda d: NamedSharding(mesh, spec_for(d.shape, d.axes, mesh)),
                     defs)


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(int(jnp.prod(jnp.asarray(l.shape))) if not hasattr(l, "size")
               else l.size for l in leaves) if leaves and is_def(leaves[0]) else \
        sum(l.size for l in leaves)


def count_defs(defs) -> int:
    leaves = jax.tree.flatten(defs, is_leaf=is_def)[0]
    total = 0
    for d in leaves:
        sz = 1
        for s in d.shape:
            sz *= s
        total += sz
    return total


def stacked(defs: Dict, n: int, axis_name: str = "layers"):
    """Add a leading stacking dim (for scan-over-layers) to every leaf."""
    return _tree_map(
        lambda d: dataclasses.replace(d, shape=(n,) + d.shape,
                                      axes=(axis_name,) + d.axes), defs)
